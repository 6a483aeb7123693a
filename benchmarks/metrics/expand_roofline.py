"""expand_roofline (%, device trace): the least time the chip could take for
the bytes the traced rounds' own trees had to read (benchmarks/
work_bestfirst.py: the root's rows and every split's smaller child, counted
by the job on the trees that were grown) over the device time of the
best-first pass, the jitted ``level_step_bestfirst`` of tree/bestfirst.py
as the trace's ``XLA Modules`` line names it (best-first pass)."""
from benchmarks import work, work_bestfirst

MODULE_PREFIX = "jit_level_step_bestfirst"


def read(ctx):
    t = ctx["trace"]
    smaller = ctx["clocks"].get("bestfirst_smaller_rows")
    if not t or not smaller:
        return None
    spent = sum(s for name, s in t["module_s"].items()
                if name.startswith(MODULE_PREFIX))
    if spent <= 0:
        return None
    rows = ctx["clocks"]["rows"]
    features = work.config_shape(ctx["config"])[1]
    least, binds = work.least_seconds(
        sum(work_bestfirst.tree_bytes(rows, s, features) for s in smaller),
        sum(work_bestfirst.tree_flops(rows, s, features) for s in smaller),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"expand_roofline: {least * 1e3:.3f} ms needed for the "
               f"{len(smaller)} traced trees (bound by {binds}) against "
               f"{spent * 1e3:.1f} ms in {MODULE_PREFIX}*")
    return 100.0 * least / spent
