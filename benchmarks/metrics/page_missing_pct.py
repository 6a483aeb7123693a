"""page_missing_pct (%, program counter): the share of the binned page's
cells that hold the sentinel of an absent entry, ``100 bins.missing /
bins.cells`` from the arguments of the run's ``dmatrix.bin`` span: the share
of the histogram's one-hot rows that are all-zero; a program without the
span or its counters gives nothing (data: sketch + binning)."""


def page_counters(ctx):
    """(cells, missing) as the run's one ``dmatrix.bin`` span counted them,
    or None (with a log line) where the ring has no such span or counter."""
    try:
        from xgboost_tpu.telemetry.spans import recent
    except ImportError:
        ctx["log"]("page counters: this program has no spans.recent")
        return None
    built = [r for r in recent("dmatrix.bin") if r.get("bins.cells")]
    if not built:
        ctx["log"]("page counters: the ring holds no dmatrix.bin span with "
                   "bins.cells")
        return None
    return int(built[-1]["bins.cells"]), int(built[-1]["bins.missing"])


def read(ctx):
    got = page_counters(ctx)
    if got is None:
        return None
    cells, missing = got
    ctx["log"](f"page_missing_pct: bins.cells {cells}, bins.missing {missing}")
    return 100.0 * missing / cells
