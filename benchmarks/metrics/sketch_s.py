"""sketch_s (s, program span): the ``dmatrix.sketch`` span of the run's one
``dmatrix.build``: the quantile sketch, ending at its own copy of the grid to
the host, so drained, and holding what is left of the upload (data layer:
sketch + binning)."""


def read(ctx):
    try:
        from xgboost_tpu.telemetry.spans import recent
    except ImportError:
        ctx["log"]("sketch_s: this program has no spans.recent")
        return None
    records = recent()
    builds = [r for r in records if r["name"] == "dmatrix.build"]
    inside = [r for r in records if r.get("parent") == "dmatrix.build"]
    sketches = [r for r in inside if r["name"] == "dmatrix.sketch"]
    if len(builds) != 1 or len(sketches) != 1:
        ctx["log"](f"sketch_s: the ring holds {len(builds)} dmatrix.build and "
                   f"{len(sketches)} dmatrix.sketch spans, not one of each")
        return None
    ctx["log"](f"sketch_s: dmatrix.build {builds[0]['dur_ns'] * 1e-9:.3f}s = "
               + ", ".join(f"{r['name']} {r['dur_ns'] * 1e-9:.3f}s"
                           for r in inside) + " and the rest")
    return sketches[0]["dur_ns"] * 1e-9
