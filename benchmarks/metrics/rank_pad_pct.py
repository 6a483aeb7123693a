"""rank_pad_pct (%, program counter): the share of the slots of the ranking
gradient's layout that hold no document, ``100 (1 - rank.docs /
rank.slots)``, from the arguments of the run's ``objective.group_layout``
span; a program without the span or its counters gives nothing (objective:
gradient)."""


def read(ctx):
    try:
        from xgboost_tpu.telemetry.spans import recent
    except ImportError:
        ctx["log"]("rank_pad_pct: this program has no spans.recent")
        return None
    built = [r for r in recent("objective.group_layout") if r.get("rank.slots")]
    if not built:
        ctx["log"]("rank_pad_pct: the ring holds no objective.group_layout "
                   "span with rank.slots")
        return None
    last = built[-1]
    ctx["log"]("rank_pad_pct: " + ", ".join(
        f"{k} {last[k]}" for k in sorted(last) if k.startswith("rank.")))
    return 100.0 * (1.0 - last["rank.docs"] / last["rank.slots"])
