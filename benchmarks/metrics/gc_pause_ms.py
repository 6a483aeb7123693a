"""gc_pause_ms (ms, program counter): the cycle collector's milliseconds a
round: ``gc.ns``, which the program's collector hook adds up and the loop's
top-level spans (``train.round``, ``train.after_iteration``,
``train.boundary``) carry the growth of, summed over the periods of the
window's untraced rounds (booster loop).  A period runs from one
``train.round``'s opening to the next one's; the window's last round, which
no round follows and which ends in the harness's own wait for the device, is
left out.  A program without the account or the hook gives nothing."""
from benchmarks.metrics.round_host_s import window_spans


def window_accounts(ctx):
    """The program's own account (``spans.round_account``) of the window's
    untraced rounds but the last, or None (with a log line) where the program
    has none, the ring does not hold them, or a period is not partitioned
    exactly.  Read once a run: the context keeps it."""
    if "round_accounts" not in ctx:
        ctx["round_accounts"] = _window_accounts(ctx)
    return ctx["round_accounts"]


def _window_accounts(ctx):
    try:
        from xgboost_tpu.telemetry.spans import round_account
    except ImportError:
        ctx["log"]("round account: this program has no spans.round_account")
        return None
    got = window_spans(ctx)
    if got is None:
        return None
    _ring_room(ctx, got[1])
    rounds = got[0][:-1]
    accounts = [a for a in round_account(rounds[0]) if a["round"] in rounds
                ] if rounds else []
    if not accounts or [a["round"] for a in accounts] != list(rounds):
        ctx["log"](f"round account: rounds {[a['round'] for a in accounts]} "
                   f"accounted for, rounds {list(rounds)} asked for")
        return None
    for a in accounts:
        if sum(a["self_ns"].values()) + a["gap_ns"] != a["period_ns"]:
            ctx["log"](f"round account: round {a['round']} does NOT sum to "
                       f"its period: {a}")
            return None
    ctx["log"](f"round account: {len(accounts)} periods partitioned exactly "
               f"(sum of self times + gap == period, to the nanosecond); "
               f"ms a round by name: " + ", ".join(
                   f"{name} {ns / len(accounts) * 1e-6:.3f}" for name, ns in
                   sorted(_sum_by_name(accounts).items(),
                          key=lambda kv: -kv[1])))
    return accounts


def _ring_room(ctx, records):
    """Say how much of the program's ring has been written since the
    window's first untraced round began: once that outgrows the ring, every
    ``program_span`` metric of the cell reads nothing (``window_spans`` asks
    for whole rounds)."""
    try:
        from xgboost_tpu.telemetry import flight
        size = flight._ring.maxlen
    except (ImportError, AttributeError):
        return
    first = min((r["seq0"] for r in records if "seq0" in r), default=None)
    if first is None or not size:
        return
    written = flight.seq() - first
    ctx["log"](f"span ring: {written} records since the window's first "
               f"untraced round began, of the ring's {size} "
               f"({100 * written / size:.0f}%)"
               + ("; WARNING: over 80%, a round more and the ring wraps"
                  if written > 0.8 * size else ""))


def _sum_by_name(accounts):
    total = {"(gap)": sum(a["gap_ns"] for a in accounts)}
    for a in accounts:
        for name, ns in a["self_ns"].items():
            total[name] = total.get(name, 0) + ns
    return total


def read(ctx):
    accounts = window_accounts(ctx)
    if accounts is None or any(a.get("gc_ns") is None for a in accounts):
        return None
    n = len(accounts)
    ctx["log"](f"gc_pause_ms: {sum(a['gc_collections'] for a in accounts)} "
               f"collections, {sum(a['gc_gen2'] for a in accounts)} of the "
               f"oldest generation, in {n} periods")
    return sum(a["gc_ns"] for a in accounts) / n * 1e-6
