"""cat_split_pct (%, program counter): the share of the splits of the
window's untraced rounds made on a categorical column, ``100
splits.categorical / splits`` from the round spans' counters: how far the
categorical scan and the membership route carry the cell.  A program
without the counter gives nothing (level step)."""
from benchmarks.metrics.hist_row_visits import round_counters


def read(ctx):
    got = round_counters(ctx, "splits.categorical", "splits")
    if got is None or not got[0][1]:
        return None
    (categorical, splits), rounds = got
    ctx["log"](f"cat_split_pct: {categorical} of {splits} splits in {rounds} "
               f"rounds on a categorical column")
    return 100.0 * categorical / splits
