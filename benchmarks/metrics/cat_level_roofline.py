"""cat_level_roofline (%, device trace): the least time the chip could take
for the bytes the levels of a round have to read of a page of categorical
codes (benchmarks/work_cat.py: each column's bin index at the bits its bins
need, past one byte where a column has more categories than 256) over the
device time of the level programs, both per traced round (level step)."""
from benchmarks import work, work_cat
from benchmarks.metrics.level_roofline import MODULE_PREFIX


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    spent = sum(s for name, s in t["module_s"].items()
                if name.startswith(MODULE_PREFIX))
    rounds = len(ctx["clocks"].get("traced_round_s", []))
    cfg = ctx["config"]
    if spent <= 0 or not rounds or "columns" not in cfg["dataset"]:
        return None
    rows = ctx["clocks"].get("rows", int(cfg["dataset"]["rows"]))
    trees = int(cfg.get("guarantees", {}).get("trees_per_round", 1))
    least, binds = work.least_seconds(
        trees * work_cat.level_bytes(cfg, rows),
        trees * work_cat.level_flops(cfg, rows),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"cat_level_roofline: {least * 1e3:.3f} ms needed a round for "
               f"columns of {work_cat.column_bits(cfg)} bits (bound by "
               f"{binds}) against {spent / rounds * 1e3:.1f} ms in "
               f"{MODULE_PREFIX}* a traced round")
    return 100.0 * least / (spent / rounds)
