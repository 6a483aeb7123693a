"""round_unnamed_pct (%, program span): the share of the host's own time a
round that no span names: ``100 x`` the account's ``unnamed_ns`` (the self
time of the loop's container spans, ``train.round``,
``train.after_iteration`` and ``update.update_tree``, plus what lies between
the loop's top-level spans: the program's own list, ``spans.CONTAINERS``)
over (the period less ``grow.wait_device``), from the program's account of
the periods of the window's untraced rounds but the last (booster loop).  A
program without the account gives nothing."""
from benchmarks.metrics.gc_pause_ms import window_accounts


def read(ctx):
    accounts = window_accounts(ctx)
    if accounts is None or any("unnamed_ns" not in a for a in accounts):
        return None
    unnamed = sum(a["unnamed_ns"] for a in accounts)
    own = sum(a["period_ns"] - a["self_ns"].get("grow.wait_device", 0)
              for a in accounts)
    n = len(accounts)
    ctx["log"](f"round_unnamed_pct: {unnamed / n * 1e-6:.3f} ms of the "
               f"host's own {own / n * 1e-6:.3f} ms a round in no span but a "
               f"container")
    return 100.0 * unnamed / own if own > 0 else None
