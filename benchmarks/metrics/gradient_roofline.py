"""gradient_roofline (%, device trace): the least time the chip could take
for the documents and pairs a round's ranking gradient has to touch
(benchmarks/work_rank.py, counted from the run's own groups) over
``gradient_s`` (objective: gradient)."""
from benchmarks import work, work_rank
from benchmarks.metrics import gradient_s


def read(ctx):
    spent = gradient_s.read(ctx)
    docs, pairs = (ctx["clocks"].get(k) for k in ("rank_docs", "rank_pairs"))
    if not spent or not docs:
        return None
    least, binds = work.least_seconds(
        work_rank.gradient_bytes(docs), work_rank.gradient_flops(pairs),
        work.load_peaks(ctx["device_kind"]))
    ctx["log"](f"gradient_roofline: {least * 1e6:.1f} us needed a round for "
               f"{docs} documents and {pairs} pairs (bound by {binds}) "
               f"against {spent * 1e3:.1f} ms in "
               f"{gradient_s.MODULE_PREFIX}* a traced round")
    return 100.0 * least / spent
