"""level_present_roofline (%, device trace): the least time the chip could
take for the bytes of the **present** entries of the rows the levels of a
round have to visit (benchmarks/work_missing.py, the present share from the
program's own count over its page) over the device time of the level
programs, both per traced round: what ``level_roofline`` would read of a
page that kept a row stride under ``F``.  A program without the page's
counters gives nothing (level step)."""
from benchmarks import work, work_missing
from benchmarks.metrics.level_roofline import MODULE_PREFIX
from benchmarks.metrics.page_missing_pct import page_counters


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    spent = sum(s for name, s in t["module_s"].items()
                if name.startswith(MODULE_PREFIX))
    rounds = len(ctx["clocks"].get("traced_round_s", []))
    got = page_counters(ctx)
    if spent <= 0 or not rounds or got is None:
        return None
    cells, missing = got
    present = 1.0 - missing / cells
    rows, features, depth, trees = work.config_shape(ctx["config"])
    rows = ctx["clocks"].get("rows", rows)
    least, binds = work.least_seconds(
        trees * work_missing.level_present_bytes(rows, features, depth, present),
        trees * work_missing.level_present_flops(rows, features, depth, present),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"level_present_roofline: {least * 1e3:.3f} ms needed a round "
               f"for the {present:.4f} of the cells that are present (bound "
               f"by {binds}) against {spent / rounds * 1e3:.1f} ms in "
               f"{MODULE_PREFIX}* a traced round")
    return 100.0 * least / (spent / rounds)
