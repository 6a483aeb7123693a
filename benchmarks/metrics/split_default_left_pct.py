"""split_default_left_pct (%, program counter): the share of the splits of
the window's untraced rounds that send their absent rows left, ``100
splits.default_left / splits`` from the round spans' counters.  0 or 100
says that the direction is not being learned; anything between is neither
better nor worse (``better`` is ``higher`` for want of a third value); a
program without the counters gives nothing (level step)."""
from benchmarks.metrics.hist_row_visits import round_counters


def read(ctx):
    got = round_counters(ctx, "splits.default_left", "splits")
    if got is None or not got[0][1]:
        return None
    left, splits = got[0]
    ctx["log"](f"split_default_left_pct: {left} of {splits} splits send "
               f"absent rows left")
    return 100.0 * left / splits
