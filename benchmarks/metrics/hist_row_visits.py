"""hist_row_visits (x rows, program counter): the rows the program gave to
the histogram a tree, over the rows of the matrix: the passes over all rows
that a tree cost.  From the ``bestfirst.hist_rows`` counter that the
best-first grower adds to the round span's arguments, summed over the
window's untraced rounds; a program without the counter gives nothing
(best-first pass)."""
from benchmarks.metrics.round_host_s import window_spans


def round_counters(ctx, *names):
    """The sums of the round spans' counters ``names`` over the window's
    untraced rounds and the number of those rounds, or None where the ring
    or a counter is missing."""
    got = window_spans(ctx)
    if got is None:
        return None
    rounds, records = got
    spans = [r for r in records if r["name"] == "train.round"]
    if not spans or any(n not in r for r in spans for n in names):
        ctx["log"](f"the round spans carry no {' '.join(names)}")
        return None
    return [sum(r[n] for r in spans) for n in names], len(rounds)


def read(ctx):
    got = round_counters(ctx, "bestfirst.hist_rows", "bestfirst.passes")
    if got is None:
        return None
    (hist_rows, passes), rounds = got
    trees = rounds * int(ctx["config"].get("guarantees", {}).get(
        "trees_per_round", 1))
    ctx["log"](f"hist_row_visits: {passes} passes and {hist_rows} rows given "
               f"to the histogram in {trees} trees")
    return hist_rows / (trees * ctx["clocks"]["rows"])
