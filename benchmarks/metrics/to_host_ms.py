"""to_host_ms (ms, program span): the mean ``grow.to_host`` span a tree over
the window's untraced rounds: the twelve copies of a finished tree to the
host, the device already drained (booster loop)."""
from benchmarks.metrics.round_host_s import window_spans


def read(ctx):
    got = window_spans(ctx)
    if got is None:
        return None
    copies = [r["dur_ns"] for r in got[1] if r["name"] == "grow.to_host"]
    if not copies:
        return None
    return sum(copies) / len(copies) * 1e-6
