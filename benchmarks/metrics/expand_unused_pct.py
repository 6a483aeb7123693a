"""expand_unused_pct (%, program counter): the share of the pairs of
children the program evaluated that the tree never took, ``100 (1 -
bestfirst.pairs_committed / bestfirst.pairs_evaluated)`` over the window's
untraced rounds, from the round spans' counters: nought for a serial loop;
a program without the counters gives nothing (best-first pass)."""
from benchmarks.metrics.hist_row_visits import round_counters


def read(ctx):
    got = round_counters(ctx, "bestfirst.pairs_committed",
                         "bestfirst.pairs_evaluated")
    if got is None or not got[0][1]:
        return None
    committed, evaluated = got[0]
    ctx["log"](f"expand_unused_pct: {committed} of {evaluated} evaluated "
               f"pairs committed")
    return 100.0 * (1.0 - committed / evaluated)
