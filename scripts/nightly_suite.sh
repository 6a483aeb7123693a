#!/bin/bash
# Full test suite + bench canary (SURVEY §4 nightly role).  The quick tier
# (`pytest -m quick`, <3 min) is the per-commit gate; this is the deep one.
set -e
cd "$(dirname "$0")/.."

# static-analysis gate first (docs/static_analysis.md): fail fast on
# retrace/lock/seam/metric violations before paying for the test suite;
# writes bench_out/lint_report.json for trend tracking
bash scripts/lint_gate.sh

# ThreadSanitizer smoke over the native ParallelFor pool + threaded
# kernels + concurrent dispatch (docs/native_threading.md).  The smoke
# binary itself sweeps BOTH simd levels (scalar + best detected ISA,
# native/xtb_simd.h) through every kernel section, so one run covers the
# scalar and vector paths under TSAN.  Only a toolchain WITHOUT libtsan
# skips (probed with a trivial program, so a real compile error in the
# smoke/kernels cannot masquerade as "no libtsan"); with libtsan present,
# build failures and TSAN findings both fail the nightly.
if echo 'int main(){return 0;}' | g++ -x c++ -fsanitize=thread -o /tmp/_tsan_probe - >/dev/null 2>&1; then
    rm -f /tmp/_tsan_probe
    echo "== native TSAN smoke =="
    make -C native tsan_smoke
    ./native/tsan_smoke
else
    echo "== native TSAN smoke: libtsan unavailable, skipping =="
fi

python -m pytest tests/ -q --durations=25

# lockdep-armed legs (docs/reliability.md "Lockdep witness"): the runtime
# witness watches real multi-process traffic for lock-order inversions
# and locks held across fault seams.  Any violation prints the
# XTB-LOCKDEP-VIOLATION marker on stderr at process exit — a leg passes
# only when its whole process tree stays silent.
run_lockdep_clean() {
    local log
    log=$(mktemp /tmp/xtb_lockdep_leg.XXXXXX.log)
    XGBOOST_TPU_LOCKDEP=1 "$@" >"$log" 2>&1 || { cat "$log"; rm -f "$log"; return 1; }
    if grep -n "XTB-LOCKDEP-VIOLATION" "$log"; then
        echo "lockdep witness reported violations under: $*" >&2
        cat "$log"
        rm -f "$log"
        return 1
    fi
    tail -n 3 "$log"
    rm -f "$log"
}

# chaos soak under the armed witness: every episode additionally checks
# the lockdep_silent invariant (reliability/chaos.py), and the marker
# grep catches violations from killed child processes too
echo "== lockdep-armed chaos soak =="
run_lockdep_clean env JAX_PLATFORMS=cpu python scripts/chaos_soak.py \
    --budget-s 60 --seed "${NIGHTLY_SEED:-20260804}"

# multi-process smokes under the armed witness: tracker fan-out under a
# mid-round kill, and fleet dispatch/heartbeat traffic with a replica
# SIGKILL — the two densest lock/wire interleavings in the tree
echo "== lockdep-armed fault smoke =="
run_lockdep_clean env JAX_PLATFORMS=cpu python scripts/fault_smoke.py 4 6
echo "== lockdep-armed fleet smoke =="
run_lockdep_clean env JAX_PLATFORMS=cpu python scripts/fleet_smoke.py 2 60

# telemetry smoke: a short traced training run must leave a parseable JSONL
# whose span names cover the per-round phases (docs/observability.md)
TRACE_OUT=$(mktemp /tmp/xtb_telemetry_smoke.XXXXXX.jsonl)
XGBOOST_TPU_TRACE="$TRACE_OUT" JAX_PLATFORMS=cpu python - "$TRACE_OUT" <<'EOF'
import json, sys
import numpy as np
import xgboost_tpu as xtb
from xgboost_tpu import telemetry

rng = np.random.default_rng(0)
X = rng.normal(size=(2000, 12)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
d = xtb.DMatrix(X, label=y)
cb = telemetry.TelemetryCallback()
xtb.train({"objective": "binary:logistic", "max_depth": 4}, d, 5,
          evals=[(d, "train")], callbacks=[cb], verbose_eval=False)
telemetry.trace.flush()

events = [json.loads(l) for l in open(sys.argv[1])]  # every line must parse
assert events, "trace is empty"
assert all(set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
           for e in events), "malformed trace event"
names = "\n".join(sorted({e["name"] for e in events}))
for needle in ("build_hist", "eval_split", "update_tree", "eval.",
               "update.gradient"):
    assert needle in names, f"phase {needle!r} missing from trace:\n{names}"
assert len(cb.history) == 5 and cb.compiles_steady == 0, \
    f"steady-state retraces: {cb.compiles_steady}"
assert "xtb_phase_seconds_bucket" in telemetry.render_prometheus()
print(f"telemetry smoke OK: {len(events)} events, "
      f"{len(names.splitlines())} span names, 0 steady compiles")
EOF
rm -f "$TRACE_OUT"

# profiler smoke (docs/observability.md "Profiling & roofline"): a
# traced AND profiled 5-round training run next to a 2-replica fleet —
# the merged flame view must contain non-empty folded stacks from at
# least two distinct processes (driver + replicas), and the collapsed
# render must be well-formed stackcollapse lines
XGBOOST_TPU_PROF_HZ=100 XGBOOST_TPU_TELEMETRY_INTERVAL=0.2 \
JAX_PLATFORMS=cpu python - <<'EOF'
import re
import numpy as np
import xgboost_tpu as xtb
from xgboost_tpu.serving import ServingFleet
from xgboost_tpu.telemetry import distributed, profiler

rng = np.random.default_rng(0)
X = rng.normal(size=(4000, 12)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                 "seed": 0}, xtb.DMatrix(X, label=y), 5,
                verbose_eval=False)  # train() arms the profiler
assert profiler.running() and profiler.samples() > 0, "sampler never ran"
with ServingFleet({"m": bst}, n_replicas=2, warmup_buckets=(64,)) as fl:
    import time
    for _ in range(3):
        for f in [fl.submit("m", X[:64]) for _ in range(12)]:
            f.result(timeout=60)
        time.sleep(0.3)
folded = profiler.merged_folded()
pids = {k.split(";", 1)[0] for k in folded}
assert len(pids) >= 2, f"folded stacks from only {pids}"
assert all(c > 0 for c in folded.values())
collapsed = [l for l in profiler.render_folded().splitlines()
             if l and not l.startswith("#") and not l.startswith(" ")]
assert collapsed and all(re.match(r"^\S.* \d+$", l) for l in collapsed), \
    "malformed collapsed-stack lines"
print(f"profiler smoke OK: {len(folded)} stacks from {len(pids)} "
      f"processes, {sum(folded.values())} weighted samples")
EOF

# roofline smoke (docs/observability.md "Profiling & roofline"):
# measured STREAM peak + per-kernel achieved GB/s rows for hist,
# hist_q, split, predict on two ladder configs; fails when any of the
# four headline kernels never recorded (instrumentation regression)
JAX_PLATFORMS=cpu python scripts/bench_roofline.py \
    bench_out/BENCH_ROOFLINE.json --quick

# fault-injection smoke (docs/reliability.md): 4-process train, kill rank 2
# at round 3 via the injected plan, resume from the newest valid checkpoint,
# and require final-model UBJSON parity with an uninterrupted run
JAX_PLATFORMS=cpu python scripts/fault_smoke.py 4 6

# elastic smoke (docs/reliability.md § Elastic training): 4 workers, kill
# rank 2 mid-run via the fault plan, the survivors FINISH at world 3 (no
# restart); the same plan replayed must give bitwise-identical model
# bytes; a respawned replacement is absorbed at a round boundary with the
# shard map restored from the checkpoint
JAX_PLATFORMS=cpu python scripts/elastic_smoke.py 4 8

# coordinator failover + watchdog smoke (docs/reliability.md
# § Coordinator failover & watchdog): SIGKILL the supervised journaling
# tracker mid-round -> respawn + worker re-adoption -> model bytes
# bitwise-identical to an undisturbed run (the respawn pause wall is in
# the output); then a stall leg: a rank sleeping past the watchdog
# budget gets an all-thread stack dump and is declared dead, the
# survivors finish at world N-1 — dump + recovery, no hang
JAX_PLATFORMS=cpu python scripts/failover_smoke.py 3 8

# out-of-core smoke (docs/extmem.md): 2-worker paged run through
# train(ExtMemConfig) over the tracker relay — identical model bytes on
# every rank with peak RSS under the ceiling (pages stream, the full
# matrix never materializes) — then a mid-stream decode failure injected
# at the extmem.page_load seam must fail the job loudly with the cause
# in the worker's stderr tail instead of wedging the relay
JAX_PLATFORMS=cpu python scripts/extmem_smoke.py 8 4

# serving-fleet + observability smoke (docs/serving.md "Fleet",
# docs/observability.md "Distributed observability plane"): 3 replicas
# over two models with a warm compile cache, mixed traffic from 6 client
# threads, one replica SIGKILLed mid-stream — every request must complete
# with the in-process engine's exact bits (the dead replica's in-flight
# batch reroutes), p99 recorded, and the respawn must restore fleet
# strength.  Mid-run, one /metrics scrape must return per-replica-labeled
# xtb_serve_* AND merged xtb_fleet_* series; afterwards the SIGKILL'd
# replica's driver-side flight dump must exist and the merged chrome
# trace (driver + sidecars) must pair a dispatcher fleet.request with a
# replica.execute on one request trace id across two pids
JAX_PLATFORMS=cpu python scripts/fleet_smoke.py 3 120

# observability overhead guard (docs/observability.md): train+serve walls
# with telemetry shipping on vs off on the higgs config shape, min-of-N
# with interleaved legs; fails beyond BENCH_OBS_MAX_PCT (default 5%).
# Runs with the lockdep witness explicitly OFF: the script asserts the
# raw threading factories are in place (witness-off means NOTHING is
# patched — merged-but-unarmed lockdep cannot move this gate)
XGBOOST_TPU_LOCKDEP=0 JAX_PLATFORMS=cpu \
    python scripts/bench_obs.py bench_out/BENCH_OBS.json

# composed-fault chaos soak (docs/reliability.md "Integrity & chaos"):
# >= 20 seeded multi-fault episodes round-robin across the scenario
# templates (extmem / fleet / lifecycle / online / elastic /
# tracker_kill / stall / resource / fleet_degraded / net_partition),
# each checked for no-hang, bitwise-vs-twin, fault
# accounting, zero dropped requests, and a flight dump per death; the
# run ends by replaying episode 0's seed and requiring the identical
# schedule and outcome.  Any red episode prints its one-command repro
# (--replay <scenario> <seed>).
JAX_PLATFORMS=cpu python scripts/chaos_soak.py --budget-s 120 \
    --seed "${NIGHTLY_SEED:-20260804}"

# resource-degradation smoke (docs/reliability.md "Resource pressure &
# graceful degradation"): train with the checkpoint directory on a
# tmpfs too small for the keep-last-K set — the kernel returns REAL
# ENOSPC mid-commit; the ladder must prune-retry then skip, the run
# must finish bitwise-identical to its roomy-disk twin, every committed
# checkpoint must scrub clean (no torn files under a final name), and
# the degradation must be counted + loud.  Falls back to the injected
# disk_full kind (same seam, same ladder) where tmpfs mounts are not
# permitted.
JAX_PLATFORMS=cpu python scripts/resource_smoke.py 10

# online-lifecycle smoke (docs/serving.md "Online model lifecycle"):
# serve -> continuation-train on fresh rows -> gate -> hot-swap under
# sustained traffic (zero dropped requests, post-swap bitwise-stable,
# shadow comparator scored), then the cycle replayed with a
# lifecycle.swap KILL — the manifest must still name the incumbent and a
# restarted fleet must serve its exact bits
JAX_PLATFORMS=cpu python scripts/lifecycle_smoke.py 2 60

# online-learning-loop smoke (docs/online.md): live traffic with feedback
# sampling on -> trace-keyed label join -> drift detector trips on a
# shifted distribution -> OnlineScheduler retrains + hot-swaps under
# sustained traffic (zero dropped requests); a governor-degraded forced
# retrain must DEFER while serving keeps answering; the whole loop
# replayed from the same seed must retrain the bitwise-identical model
JAX_PLATFORMS=cpu python scripts/online_smoke.py 2

# the chip smoke, rehearsed on the CPU (the smoke proper needs the chip and
# fails without it; the rehearsal's last line says "ok": false)
JAX_PLATFORMS=cpu python chip_smoke.py --rows 100000 --allow-cpu
