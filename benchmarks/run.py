"""One run of one cell of ``BENCHMARK.json``, in a new process:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data.  A cell names a configuration and a traffic
mix; their files are ``benchmarks/configs/<config>.json`` and
``benchmarks/traffic/<traffic>.json``; the traffic file names a job kind,
``benchmarks/jobs/<kind>.py`` (``setup``, ``window``, ``compare``), whose
limits are ``benchmarks/limits/<kind>.json``; a metric ``m`` is read by
``benchmarks/metrics/<m>.py``.  Nothing here names a cell, a configuration
or a metric.

With no TPU (or fewer chips than the cell asks for) the run ends with a
non-zero code and prints no result line.  ``--rehearse-rows <n>`` runs the
same code at ``n`` rows on whatever JAX finds, for rehearsal on the CPU:
its result line says ``"correct": false``, names the CPU in ``device`` and
carries no metric.

Standard output carries one line, the result; everything else (phase
seconds, cache hits, the reference's readings) goes to standard error,
whose last lines are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(text: str) -> None:
    print(f"[{time.perf_counter() - T0:8.2f}s] {text}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, found by the name in the manifest
    (a name may hold dots and dashes, so not by ``import``)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks/{kind}/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, manifest: dict = None) -> dict:
    """The cell ``workload`` with its configuration and traffic files read."""
    manifest = manifest or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"BENCHMARK.json has no workload {workload!r}: "
                         f"it has {sorted(cells)}")
    cell = dict(cells[workload])
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    cell["config"] = load_json(ROOT, files[cell["config"]])
    cell["traffic"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["manifest"] = manifest
    return cell


def load_limits(job: str) -> dict:
    """``benchmarks/limits/<job>.json``: the limit of every number the job's
    ``compare`` returns for ``correct`` (keys that start with ``_`` are notes)."""
    return {k: v for k, v in load_json(HERE, "limits", job + ".json").items()
            if not k.startswith("_")}


def cell_metrics(cell: dict, group: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in cell["manifest"][group]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_metrics(cell: dict, group: str, ctx: dict) -> dict:
    """Each metric through its own reader; one that finds nothing to read
    returns None and is left out of the line."""
    out = {}
    for m in cell_metrics(cell, group):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Tracer:
    """The JAX profiler around the traced rounds: device and host tracers,
    the Python tracer off, the trace under a fixed directory of the checkout
    that is emptied before and after."""

    def __init__(self, workload: str) -> None:
        self.dir = os.path.join(ROOT, ".bench_trace", workload)
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self, window_s: float):
        from benchmarks import trace

        try:
            planes = trace.load(self.dir)
            for name, lines in planes.items():
                log(f"trace plane {name}: " + ", ".join(
                    f"{line} ({len(ev)} events)" for line, ev in lines.items()))
            return trace.reduce_planes(planes, window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class CacheHits:
    """Counts jax's own persistent-cache events (``compiles_total()`` cannot
    tell a warm cache from a cold one on jax 0.9.0)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def find_device(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearsal:
        raise SystemExit(f"benchmarks/run.py: JAX found no TPU, its devices "
                         f"are {devs}; --rehearse-rows <n> rehearses on the "
                         f"CPU and ends with \"correct\": false")
    if len(devs) < chips:
        raise SystemExit(f"benchmarks/run.py: the cell asks for {chips} "
                         f"chips and JAX found {len(devs)}: {devs}")
    return devs


def judge(numbers: dict, limits: dict) -> list:
    """[name, number, limit, within] for every number the limits name; a
    number that is missing or not finite is not within its limit."""
    rows = []
    for name, limit in limits.items():
        got = numbers.get(name, float("nan"))
        rows.append([name, got, limit, bool(got == got and got <= limit)])
    return rows


def run_cell(workload: str, seed: int, seconds: float, trace_on: bool,
             rehearse_rows: int = None) -> dict:
    """Everything after the arguments; returns the result line's object."""
    cell = load_cell(workload)
    devs = find_device(int(cell["chips"]), bool(rehearse_rows))
    on_chip = devs[0].platform == "tpu"

    from xgboost_tpu.serving.warmcache import configure_persistent_cache

    cache_dir = configure_persistent_cache()
    seen = CacheHits()
    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace_on)}"
        f" on {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}; "
        f"compile cache at {cache_dir}"
        + ("" if on_chip else "  ** REHEARSAL: no chip, nothing below is a "
                              "device number **"))
    job = load_module("jobs", cell["traffic"]["job"])
    limits = load_limits(cell["traffic"]["job"])
    env = {"log": log, "rehearse_rows": rehearse_rows}

    state = job.setup(cell, seed, env)
    tracer = Tracer(workload) if trace_on else None
    job.window(state, seconds, tracer)
    clocks = state.clocks
    clocks["setup_s"] = clocks["opened_at"] - T0
    stats = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:int(cell["chips"])])
    log("warm rounds ended "
        + ", ".join(f"{t:.2f}s" for t in clocks.get("warm_round_ends", []))
        + " after the training call")
    log(f"set-up {clocks['setup_s']:.2f}s (of this process's "
        f"{seen.requests} compile requests {seen.hits} were cache hits); "
        f"window {clocks['window_s']:.2f}s, {state.attempted} rounds, "
        f"longest {clocks['round_max_s']:.3f}s; peak device memory "
        f"{peak / 1e9:.3f} GB of {stats.get('bytes_limit', 0) / 1e9:.1f} GB")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    ctx = {"cell": cell, "config": cell["config"], "clocks": clocks,
           "trace": None, "device_kind": devs[0].device_kind, "log": log}
    breakdown = None
    if tracer:
        reduced = tracer.reduce(clocks["traced_window_s"])
        if reduced is not None:
            ctx["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            log("device time by XLA Modules name over the traced rounds: "
                + json.dumps(reduced["module_s"]))
        elif on_chip:
            raise SystemExit("the traced rounds left no device operation "
                             "in the trace")
    metrics = {}
    if on_chip:
        metrics = read_metrics(cell, "per_layer" if trace_on else "end_to_end",
                               ctx)

    numbers = job.compare(state, env)
    rows = judge(numbers, limits)
    within = all(r[3] for r in rows)
    result = {"correct": bool(on_chip and within and state.failed == 0),
              "attempted": state.attempted, "failed": state.failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared_within_limits"] = within
    result["compared"] = {r[0]: {"value": r[1], "limit": r[2]} for r in rows}
    log(f"compared ({'all within limits' if within else 'NOT within limits'}"
        f"; failed rounds {state.failed}):")
    for name, got, limit, ok in rows:
        print(f"  {name} {got:.6e} limit {limit:.6e} "
              f"{'ok' if ok else 'OVER'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="run at this many rows on whatever JAX finds; the "
                         "result says \"correct\": false and has no metric")
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        seconds = load_json(ROOT, "BENCHMARK.json")["run_seconds"]
    result = run_cell(args.workload, args.seed, float(seconds),
                      bool(args.trace), args.rehearse_rows)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
