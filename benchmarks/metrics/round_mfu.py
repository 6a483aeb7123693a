"""round_mfu (%, host clock): the least time the chip could take for a whole
round's necessary work (benchmarks/work.py) at its peaks, over the mean wall
time of the window's untraced rounds: the whole step's share of the chip."""
from benchmarks import work


def read(ctx):
    mean = ctx["clocks"].get("round_mean_s")
    if not mean:
        return None
    rows, features, depth, trees = work.config_shape(ctx["config"])
    rows = ctx["clocks"].get("rows", rows)
    least, binds = work.least_seconds(
        work.round_bytes(rows, features, depth, trees),
        work.round_flops(rows, features, depth, trees),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"round_mfu: {least * 1e3:.3f} ms needed a round (bound by "
               f"{binds}) against {mean * 1e3:.1f} ms of wall time a round")
    return 100.0 * least / mean
