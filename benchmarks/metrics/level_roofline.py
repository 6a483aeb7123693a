"""level_roofline (%, device trace): the least time the chip could take for
the bytes the levels of a round have to read (benchmarks/work.py) over the
device time of the level programs, both per traced round.  The level
programs are the jitted ``level_step*`` of tree/grow.py as the trace's
``XLA Modules`` line names them."""
from benchmarks import work

MODULE_PREFIX = "jit_level_step"


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    spent = sum(s for name, s in t["module_s"].items()
                if name.startswith(MODULE_PREFIX))
    rounds = len(ctx["clocks"].get("traced_round_s", []))
    if spent <= 0 or not rounds:
        return None
    rows, features, depth, trees = work.config_shape(ctx["config"])
    rows = ctx["clocks"].get("rows", rows)
    least, binds = work.least_seconds(
        trees * work.level_bytes(rows, features, depth),
        trees * work.level_flops(rows, features, depth),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"level_roofline: {least * 1e3:.3f} ms needed a round "
               f"(bound by {binds}) against {spent / rounds * 1e3:.1f} ms "
               f"in {MODULE_PREFIX}* a traced round")
    return 100.0 * least / (spent / rounds)
