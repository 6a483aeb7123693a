"""round_host_s (s, program span): the mean, over the window's untraced
rounds, of the ``train.round`` span less the ``grow.wait_device`` spans
inside it: the host's own time a round, the shortest a round can get as the
loop is built (booster loop).  Read from the program's span ring in-process;
a program without the ring (or a ring that has lost the window's first
round) gives nothing."""


def window_spans(ctx):
    """(rounds, records): the window's untraced rounds and the ring's span
    records of them, or None (with a log line) where they cannot be read.
    Logs what the program compiled, loaded and traced in the window."""
    try:
        from xgboost_tpu.telemetry.spans import recent
    except ImportError:
        ctx["log"]("span metrics: this program has no spans.recent")
        return None
    traffic, clocks = ctx["cell"]["traffic"], ctx["clocks"]
    warm = int(traffic["warm_rounds"])
    traced = int(traffic["traced_rounds"]) if clocks.get("traced_round_s") else 0
    records = recent(round_from=warm)
    held = {r["round"] for r in records if r["name"] == "train.round"}
    # a window still open has no count yet: up to the newest whole round
    last = (warm + int(clocks["window_rounds"]) if "window_rounds" in clocks
            else max(held, default=-1) + 1)
    rounds = range(warm + traced, last)
    if not rounds or not held.issuperset(rounds):
        ctx["log"](f"span metrics: the ring holds rounds {sorted(held)}, the "
                   f"window's untraced rounds are {list(rounds)}")
        return None
    counts = {k: sum(r.get(k, 0) for r in records)
              for k in ("compiled", "loaded", "traced")}
    ctx["log"](f"span metrics: in rounds {warm} to {rounds[-1]} the program "
               + ", ".join(f"{k} {v}" for k, v in counts.items())
               + " (all three should read 0)")
    return rounds, [r for r in records if r["round"] in rounds]


def read(ctx):
    got = window_spans(ctx)
    if got is None:
        return None
    rounds, records = got
    whole = sum(r["dur_ns"] for r in records if r["name"] == "train.round")
    waited = sum(r["dur_ns"] for r in records
                 if r["name"] == "grow.wait_device")
    ctx["log"](f"round_host_s: {len(rounds)} rounds of "
               f"{whole / len(rounds) * 1e-9:.4f}s, of which "
               f"{waited / len(rounds) * 1e-9:.4f}s waiting for the device")
    return (whole - waited) / len(rounds) * 1e-9
