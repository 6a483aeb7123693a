"""What a span, the collector's callback pair, the clock reads on the loop's
top-level spans and the loop's watch cost on this host, in microseconds (no
device; PERF.md §6, PR 36, has the chip's host):

    chiprun -- python scripts/probe_span_costs.py
"""
import gc
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())
from xgboost_tpu.telemetry import pauses, spans  # noqa: E402
from xgboost_tpu.telemetry.compile import counting  # noqa: E402

N = 100_000


def per_call(fn, n=N):
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter_ns()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter_ns() - t) / n / 1e3)
    return best


def one_span():
    with spans.span("cost.span"):
        pass


def one_wait():
    with spans.wait_span("cost.wait"):
        pass


def one_counted():
    with counting(spans.span("cost.counted")):
        pass


info = {"generation": 0, "collected": 0, "uncollectable": 0}


def gc_pair():
    pauses._on_gc("start", info)
    pauses._on_gc("stop", info)


def clocks():
    time.thread_time_ns()
    time.process_time_ns()
    pauses.resource.getrusage(pauses._RUSAGE_THREAD)


gc.disable()
print(f"one span {per_call(one_span):.2f} us; one wait span (the thread's "
      f"clock read twice) {per_call(one_wait):.2f} us; one span under counting "
      f"{per_call(one_counted):.2f} us; the collector's callback pair "
      f"{per_call(gc_pair):.2f} us; the three clock reads "
      f"{per_call(clocks):.2f} us; pauses.read {per_call(pauses.read):.2f} us")
watch = pauses.RoundWatch()
sp = spans.step_span("train.round", 0)


def steady_round():
    watch.tops = [sp, sp, sp, sp]
    watch.round, watch.t0 = 0, 0
    sp.t0 = 1_000_000_000
    watch.opened(sp)


for _ in range(16):
    steady_round()
print(f"the watch, a steady round: {per_call(steady_round, 20_000):.2f} us")
