"""A round's period partitioned by what the program records with no switch
(telemetry/spans.py ``round_account``), the pauses no ``with`` can bracket
(telemetry/pauses.py: the collector's hook, the clocks on the loop's
top-level spans) and the slow round that says why (``RoundWatch``).

The CPU rounds get a floor of their own (a callback that sleeps every round:
0.1 s where a stall of 0.4 s is planted, 0.3 s where nothing may fire), so
that a steady run is steady under the six workers; assertions are on what
grew and by roughly how much against the stall that was planted, never on a
tight time, and a round that the machine's load makes long beside the
planted one is let be."""
import collections
import gc
import sys
import threading
import time
import types

import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu import telemetry
from xgboost_tpu.callback import TrainingCallback
from xgboost_tpu.telemetry import flight, pauses, profiler, spans
from xgboost_tpu.utils import logging as xtb_logging

MS = 1_000_000
PARAMS = {"objective": "binary:logistic", "max_depth": 2, "max_bin": 16}
STALL_ROUND, STALL_S = 5, 0.4


def _data(rows=300, cols=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    return xtb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))


class Floor(TrainingCallback):
    """``floor_s`` every round, and ``stall`` once, in round STALL_ROUND."""

    def __init__(self, stall=None, floor_s=0.1):
        self.stall, self.floor_s = stall, floor_s

    def after_iteration(self, model, epoch, evals_log):
        time.sleep(self.floor_s)
        if self.stall is not None and epoch == STALL_ROUND:
            self.stall()
        return False


@pytest.fixture
def sampler():
    """The wall sampler at 50 Hz for the test, whatever armed it before."""
    profiler.stop()
    profiler.clear()
    assert profiler.start(hz=50)
    yield
    profiler.stop()
    profiler.clear()


@pytest.fixture
def lines():
    """What the program logged, in place of standard error."""
    got = []
    xtb_logging.register_log_callback(got.append)
    yield got
    xtb_logging.register_log_callback(None)


def _slow_events():
    return [e["detail"] for e in flight.events()
            if e["kind"] == "event" and e["name"] == "train.slow_round"]


def _put(name, t0, dur, **detail):
    flight.record("span", name, t0_ns=t0, dur_ns=dur, **detail)


def _hand_built_round(rnd, t0, wait=600 * MS, with_gc=False):
    """One round's records as the loop leaves them, children before parents:
    a round of 1,000 ms (a gradient of 30, a tree step of 900 holding a
    set-up of 10, two levels of 5, a wait that spun for 4 and a copy of 8
    that computed for 2) 10 ms of nothing, callbacks of 40 ms, 5 of nothing,
    a boundary of 2."""
    seq0 = flight.seq()
    in_round = dict(round=rnd)
    _put("train.before_iteration", t0 + 1 * MS, 1 * MS, parent="train.round", **in_round)
    _put("update.gradient", t0 + 10 * MS, 30 * MS, parent="train.round", **in_round)
    tree = t0 + 50 * MS
    _put("grow.setup", tree + 1 * MS, 10 * MS, parent="update.update_tree", **in_round)
    for d in (0, 1):
        _put("grow.build_hist+eval_split", tree + (20 + 10 * d) * MS, 5 * MS,
             parent="update.update_tree", depth=d, **in_round)
    _put("grow.wait_device", tree + 50 * MS, wait, parent="update.update_tree",
         cpu_ns=4 * MS, **in_round)
    if with_gc:  # a pause inside the wait's span: it overlaps, it is no child
        _put("host.gc", tree + 60 * MS, 70 * MS, generation=2, collected=9, **in_round)
    _put("grow.to_host", tree + 50 * MS + wait, 8 * MS, parent="update.update_tree",
         cpu_ns=2 * MS, **in_round)
    _put("update.update_tree", tree, 900 * MS, parent="train.round", **in_round)
    _put("train.round", t0, 1000 * MS, seq0=seq0, compiled=0, loaded=0, traced=0,
         cpu_ns=100 * MS, proc_cpu_ns=150 * MS, ctx_invol=1, majflt=0,
         **{"gc.ns": 70 * MS if with_gc else 0, "gc.collections": int(with_gc),
            "gc.gen2": int(with_gc)}, **in_round)
    _put("train.after_iteration", t0 + 1010 * MS, 40 * MS, compiled=0, loaded=0,
         traced=0, cpu_ns=5 * MS, proc_cpu_ns=5 * MS, ctx_invol=0, majflt=0,
         **{"gc.ns": 0, "gc.collections": 0, "gc.gen2": 0}, **in_round)
    _put("train.boundary", t0 + 1055 * MS, 2 * MS, next=rnd + 1, compiled=0,
         loaded=0, traced=0, cpu_ns=1 * MS, proc_cpu_ns=1 * MS, ctx_invol=0,
         majflt=0, **{"gc.ns": 0, "gc.collections": 0, "gc.gen2": 0}, **in_round)
    return t0 + 1060 * MS  # where the next round opens


def _exact(acct):
    return sum(acct["self_ns"].values()) + acct["gap_ns"] == acct["period_ns"]


def test_partition_of_hand_built_rounds_sums_to_the_period():
    flight.clear()
    t = 5_000 * MS
    for rnd in (3, 4, 5):
        t = _hand_built_round(rnd, t, with_gc=(rnd == 4))
    accounts = spans.round_account(3)
    assert [a["round"] for a in accounts] == [3, 4, 5] and all(map(_exact, accounts))
    a = accounts[1]
    # to the next round's opening; the last round's to its last top-level span
    assert a["period_ns"] == 1060 * MS and accounts[2]["period_ns"] == 1057 * MS
    assert a["self_ns"] == {
        "train.round": (1000 - 1 - 30 - 900) * MS, "train.before_iteration": 1 * MS,
        "update.gradient": 30 * MS,
        "update.update_tree": (900 - 10 - 10 - 600 - 8) * MS, "grow.setup": 10 * MS,
        "grow.build_hist+eval_split": 10 * MS, "grow.wait_device": 600 * MS,
        "grow.to_host": 8 * MS, "train.after_iteration": 40 * MS,
        "train.boundary": 2 * MS}
    assert a["gap_ns"] == (10 + 5 + 3) * MS
    # the collector's pause stands beside the partition, not in it
    assert [(r["generation"], r["dur_ns"]) for r in a["host_gc"]] == [(2, 70 * MS)]
    assert "host.gc" not in a["self_ns"] and accounts[0]["host_gc"] == []
    assert (a["gc_ns"], a["gc_collections"], a["gc_gen2"]) == (70 * MS, 1, 1)
    assert (a["cpu_ns"], a["proc_cpu_ns"], a["ctx_invol"], a["majflt"]) == (
        106 * MS, 156 * MS, 1, 0)
    # off the CPU outside the waits: what the waits leave of the period less
    # what the waits leave of the CPU time
    assert (a["waited_ns"], a["waited_cpu_ns"]) == (608 * MS, 6 * MS)
    assert a["offcpu_ns"] == ((1060 - 608) - (106 - 6)) * MS
    assert a["unnamed_ns"] == (69 + 272 + 40 + 18) * MS
    assert not a["warm"] and (a["compiled"], a["loaded"], a["traced"]) == (0, 0, 0)
    assert a["session_edge"] is None


def test_a_wait_inside_a_wait_is_on_the_outer_ones_clock():
    flight.clear()
    seq0, rnd = flight.seq(), dict(round=0)
    _put("eval.predict", 1045 * MS, 20 * MS, parent="eval.eval_set", cpu_ns=3 * MS, **rnd)
    _put("eval.eval_set", 1040 * MS, 50 * MS, parent="train.after_iteration",
         cpu_ns=25 * MS, **rnd)
    _put("train.round", 0, 1000 * MS, seq0=seq0, cpu_ns=10 * MS, **rnd)
    _put("train.after_iteration", 1000 * MS, 100 * MS, cpu_ns=30 * MS, **rnd)
    a, = spans.round_account(0)
    assert _exact(a) and (a["waited_ns"], a["waited_cpu_ns"]) == (50 * MS, 25 * MS)
    assert a["offcpu_ns"] == ((1100 - 50) - (40 - 25)) * MS


def test_the_account_is_of_one_threads_spans():
    """The ring is the process's: in-process workers each run a loop, and a
    period made of one's round and another's boundary would mean nothing."""
    flight.clear()

    def two_rounds():
        for rnd in (0, 1):
            with spans.step_span("train.round", rnd):
                with spans.wait_span("grow.wait_device"):
                    time.sleep(0.01)
            with spans.span("train.after_iteration", round=rnd):
                pass

    other = threading.Thread(target=two_rounds)
    other.start()
    two_rounds()
    other.join()
    assert len(spans.recent("train.round")) == 4
    for accounts in (spans.round_account(0), spans.round_account(0, other.ident)):
        assert [a["round"] for a in accounts] == [0, 1] and all(map(_exact, accounts))
        assert accounts[0]["self_ns"].keys() == {
            "train.round", "grow.wait_device", "train.after_iteration"}
    mine, theirs = spans.round_account(0), spans.round_account(0, other.ident)
    assert mine[0]["period_ns"] != theirs[0]["period_ns"]
    wait, = [r for r in spans.recent("grow.wait_device")
             if r["tid"] == other.ident and r["round"] == 1]
    assert 0 <= wait["cpu_ns"] < wait["dur_ns"]


def test_a_wrapped_ring_gives_fewer_rounds_and_never_part_of_one(monkeypatch):
    monkeypatch.setattr(flight, "_ring", collections.deque(maxlen=30))
    t = 1_000 * MS
    for rnd in range(4):
        t = _hand_built_round(rnd, t)
    assert flight.events()[0]["seq"] > 0  # it wrapped, inside round 1
    accounts = spans.round_account(0)
    assert [a["round"] for a in accounts] == [2, 3] and all(map(_exact, accounts))
    assert accounts[0]["period_ns"] == 1060 * MS


def test_records_without_the_counters_give_none_not_nought():
    flight.clear()
    for rnd in (0, 1):
        with spans.step_span("train.round", rnd):
            with spans.span("grow.wait_device"):
                pass
        with spans.span("train.after_iteration", round=rnd):
            pass
    accounts = spans.round_account(0)
    assert len(accounts) == 2 and all(map(_exact, accounts))
    for key in ("cpu_ns", "offcpu_ns", "gc_ns", "gc_gen2", "ctx_invol", "compiled"):
        assert accounts[0][key] is None
    assert accounts[0]["warm"] is False
    # a round with no callbacks yet has no period
    with spans.step_span("train.round", 2):
        pass
    assert [a["round"] for a in spans.round_account(0)] == [0, 1]


def test_every_round_of_a_real_train_is_partitioned_exactly():
    flight.clear()
    cb = telemetry.TelemetryCallback(enable_spans=False)
    xtb.train(PARAMS, _data(), 8, verbose_eval=False, callbacks=[cb])
    accounts = spans.round_account(0)
    assert [a["round"] for a in accounts] == list(range(8))
    assert all(map(_exact, accounts))
    opened = [r["t0_ns"] for r in spans.recent("train.round", round_from=0)]
    assert [a["period_ns"] for a in accounts[:-1]] == list(np.diff(opened))
    steady = accounts[4]
    assert {"train.round", "train.before_iteration", "train.after_iteration",
            "train.boundary", "update.prepare", "update.sync_margin",
            "update.gradient", "update.update_tree",
            "update.sample", "update.class_gradient", "grow.setup",
            "grow.build_hist+eval_split", "grow.margin", "grow.wait_device",
            "grow.to_host", "tree.from_grown"} <= set(steady["self_ns"])
    assert 0 <= steady["unnamed_ns"] < steady["period_ns"]
    assert steady["cpu_ns"] > 0 and steady["gc_ns"] >= 0
    assert accounts[0]["warm"]  # it traced, at the least
    # the optional callback carries the same entries, one a round; its
    # phases stay, and it writes no event of its own for a round
    assert [rec["account"]["round"] for rec in cb.history] == list(range(8))
    assert cb.history[4]["account"] == steady
    assert cb.history[4]["phases"]["train.round"]["count"] == 1
    assert not [e for e in flight.events()
                if e["kind"] == "event" and e["name"] == "train.round"]


def test_the_loops_top_level_spans_carry_the_clocks_and_the_collector():
    flight.clear()
    xtb.train(PARAMS, _data(), 3, verbose_eval=False)
    for name in ("train.round", "train.after_iteration", "train.boundary"):
        rec = spans.recent(name)[-1]
        for key in ("cpu_ns", "proc_cpu_ns", "gc.ns", "gc.collections", "gc.gen2",
                    "compiled", "loaded", "traced"):
            assert rec[key] >= 0, (name, key)
        if sys.platform.startswith("linux"):
            assert rec["ctx_invol"] >= 0 and rec["majflt"] >= 0
    rnd = spans.recent("train.round")[-1]
    assert 0 < rnd["cpu_ns"] <= rnd["proc_cpu_ns"] + MS
    # the boundary lies in the period of the round it follows
    assert [(r.get("round"), r["next"]) for r in spans.recent("train.boundary")] \
        == [(None, 0), (0, 1), (1, 2)]
    assert "parent" not in spans.recent("train.boundary")[-1]


def test_the_collectors_hook_counts_all_and_records_the_old_and_the_long(monkeypatch):
    pauses.install()
    pauses.install()  # idempotent
    assert gc.callbacks.count(pauses._on_gc) == 1
    flight.clear()
    before = pauses.read()[4:7]
    young = {"generation": 0, "collected": 3, "uncollectable": 0}
    for gen in (0, 1, 0):
        pauses._on_gc("start", dict(young, generation=gen))
        pauses._on_gc("stop", dict(young, generation=gen))
    n, ns, old = (a - b for a, b in zip(pauses.read()[4:7], before))
    assert (n, old) == (3, 0) and 0 <= ns < 3 * pauses.GC_RECORD_NS
    pauses.since(pauses.read())
    assert spans.recent("host.gc") == []  # the young ones write nothing
    real, fake = time.perf_counter_ns, iter([10 * MS, 15 * MS])

    def clock():  # a young collection of 5 ms, as this thread reads the time
        on_main = threading.current_thread() is threading.main_thread()
        return next(fake) if on_main else real()

    monkeypatch.setattr(pauses.time, "perf_counter_ns", clock)
    pauses._on_gc("start", dict(young, generation=1))
    pauses._on_gc("stop", dict(young, generation=1))
    monkeypatch.undo()
    # the hook itself writes nothing into the ring (it may run under the
    # ring's lock): the next top-level span does, as it ends
    assert spans.recent("host.gc") == []
    pauses.since(pauses.read())
    rec, = spans.recent("host.gc")
    assert (rec["generation"], rec["collected"], rec["t0_ns"], rec["dur_ns"]) == (
        1, 3, 10 * MS, 5 * MS)
    assert "round" not in rec
    # a real collection of the oldest generation, inside a round
    from xgboost_tpu.telemetry.compile import counting

    with counting(spans.step_span("train.round", 7)):
        gc.collect()
    old_one = spans.recent("host.gc")[-1]
    assert old_one["generation"] == 2 and old_one["round"] == 7
    assert old_one["dur_ns"] > 0
    assert pauses.read()[6] >= before[2] + 1
    assert spans.recent("train.round")[-1]["gc.gen2"] >= 1


def test_a_collection_under_the_rings_lock_does_not_hang():
    """The collector runs its callbacks in the thread whose allocation set
    it off, and that thread may hold ``flight._lock`` (``record`` and
    ``events`` allocate under it): a hook that wrote into the ring there
    would wait for its own thread for ever."""
    pauses.install()
    flight.clear()
    done = threading.Event()

    def collect_under_the_lock():
        with flight._lock:
            gc.collect()  # generation 2: a host.gc record is due
        done.set()

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    assert done.wait(30), "the collector's hook waits for the ring's lock"
    worker.join()
    assert spans.recent("host.gc") == []
    pauses.since(pauses.read())
    assert spans.recent("host.gc")[-1]["generation"] == 2


def _watch_of(periods_ms, warm=()):
    """A RoundWatch fed rounds of these periods (``warm``: {round: what its
    span counted}); returns it and what it called slow, as (round, period,
    median)."""
    called = []
    watch = pauses.RoundWatch()

    def tell():  # as the loop does at its boundary, and finished() at the end
        if watch.slow is not None:
            (rnd, _, period, median), watch.slow = watch.slow, None
            called.append((rnd, period // MS, median // MS))

    watch.tell = tell
    t = 0
    for rnd, ms in enumerate(periods_ms):
        sp = spans.step_span("train.round", rnd)
        sp.t0, sp.dur = t, ms * MS
        sp.args.update(compiled=0, loaded=0, traced=0)
        if rnd in warm:
            sp.args[warm[rnd]] = 1
        watch.tell()
        watch.top(sp)
        watch.opened(sp)
        t += ms * MS
    watch.finished()
    return watch, called


@pytest.mark.parametrize("periods, warm, slow", [
    ([100, 100, 100, 130, 100], (), [(3, 130, 100)]),      # over 1.2 x and 20 ms
    ([100, 100, 100, 119, 100], (), []),                   # under a fifth
    ([5, 5, 5, 24, 5], (), []),                            # x5, but under 20 ms
    ([5, 5, 5, 26, 5], (), [(3, 26, 5)]),
    ([900, 100, 130, 100], {0: "compiled"}, []),           # one steady round behind
    ([900, 100, 100, 400, 100, 130], {0: "traced", 3: "loaded"},
     [(5, 130, 100)]),                                     # a warm round: never
    ([100, 100, 100, 900, 100], {3: "session_edge"}, []),  # nor a profile's stop
    ([100, 100, 100, 100, 130], (), [(4, 130, 100)]),      # the last round too
])
def test_the_watch_calls_slow_what_is_a_fifth_and_20_ms_over_the_median(
        periods, warm, slow):
    watch, called = _watch_of(periods, warm)
    assert called == slow
    assert len(watch.periods) == len(periods) - len(warm)  # steady ones only


def test_the_watch_keeps_sixteen_periods_and_forgets_on_reset():
    watch, called = _watch_of([50] * 40)
    assert called == [] and len(watch.periods) == 16
    watch.reset()
    assert not watch.periods and watch.round is None


def test_a_profiler_session_that_begins_or_ends_marks_the_span(monkeypatch):
    # jax.profiler has no public query: this JAX still has what is read
    assert hasattr(pauses._session_state, "profile_session")
    state = types.SimpleNamespace(profile_session=None)
    monkeypatch.setattr(pauses, "_session_state", state)
    meter = pauses.read()
    assert "session_edge" not in pauses.since(meter)
    state.profile_session = object()
    assert pauses.since(meter)["session_edge"] == 1
    assert "session_edge" not in pauses.since(pauses.read())  # wholly inside one
    flight.clear()
    from xgboost_tpu.telemetry.compile import counting

    with spans.step_span("train.round", 0):
        pass
    with counting(spans.span("train.after_iteration", round=0)):
        state.profile_session = None  # a callback stopped the session
    acct, = spans.round_account(0)
    assert acct["session_edge"] == 1 and acct["warm"]


def very_sleepy_stall():
    time.sleep(STALL_S)


def very_busy_stall():
    end = time.thread_time() + STALL_S  # on the CPU for that long, whatever
    while time.thread_time() < end:     # the machine's load makes of the wall
        sum(range(100))


STALL_NS = int(STALL_S * 1e9)


def _check_sleep(ev, excess):
    assert ev["offcpu_ns"] >= 0.75 * STALL_NS   # off the CPU, in no named wait
    assert ev["cpu_ns"] < 0.5 * STALL_NS
    # the sampler kept ticking through the stall (a held lock would have kept
    # it out for all of it) and saw the frame
    assert ev["ticks"] >= 3 and ev["tick_late_ns"] < excess
    assert any("very_sleepy_stall" in stack.split("<")[0]
               for stack, n in ev["tick_frames"])


def _check_busy(ev, excess):
    assert ev["cpu_ns"] >= 0.85 * STALL_NS      # on the CPU: all of the stall
    assert any("very_busy_stall" in stack for stack, n in ev["tick_frames"])


def _check_collector(ev, excess):
    assert ev["gc_gen2"] >= 1
    old = [r for r in spans.recent("host.gc")
           if r["generation"] == 2 and r.get("round") == STALL_ROUND]
    assert old and [2, old[-1]["dur_ns"]] in ev["host_gc"]
    pause = old[-1]["dur_ns"]
    assert pause >= 30 * MS and pause <= ev["gc_ns"] <= 1.2 * excess + 20 * MS
    assert dict(ev["grew"])["train.after_iteration"] >= 0.8 * pause
    # the sampler needs the lock the collector holds: it woke late by about
    # the pause (a tick is due every 20 ms)
    assert ev["tick_late_ns"] >= pause - 40 * MS


@pytest.mark.parametrize("stall, check", [
    (very_sleepy_stall, _check_sleep),
    (very_busy_stall, _check_busy),
    (gc.collect, _check_collector),
], ids=["sleep", "busy", "collector"])
def test_a_planted_stall_is_named_rightly_by_the_slow_round(
        stall, check, sampler, lines):
    d = _data()
    xtb.train(PARAMS, d, 2, verbose_eval=False)  # every program compiled
    heap = None
    if stall is gc.collect:
        # a million cyclic containers for the oldest generation to walk;
        # collected once now, so that no young collection meets them
        gc.disable()
        heap = [[] for _ in range(1_000_000)]
        for item in heap:
            item.append(item)
        gc.enable()
        gc.collect()
    flight.clear()
    try:
        xtb.train(PARAMS, d, 8, verbose_eval=False, callbacks=[Floor(stall)])
    finally:
        del heap
    events = [ev for ev in _slow_events() if ev["round"] == STALL_ROUND]
    ev, = events
    excess = ev["period_ns"] - ev["median_ns"]
    assert 100 * MS <= ev["median_ns"] <= 500 * MS
    if stall is not gc.collect:
        assert excess >= 0.8 * STALL_NS
    # the callbacks' span holds the excess, against a steady round's
    assert ev["against"] in range(STALL_ROUND)
    grew = dict(ev["grew"])
    if stall is not gc.collect:
        assert grew["train.after_iteration"] >= 0.8 * STALL_NS
    check(ev, excess)
    assert set(ev["device_memory"]) <= {"bytes_in_use", "largest_free_block_bytes",
                                        "num_allocs"}
    # one line in the run's log, at the default verbosity
    said, = [line for line in lines if line.startswith(
        f"WARNING: train.slow_round round {STALL_ROUND}: period ")]
    assert "train.after_iteration +" in said and "ctx_invol" in said
    # told at the boundary after the round that followed, outside train.round
    ring = flight.events()
    at, = [e["seq"] for e in ring if e["kind"] == "event"
           and e["name"] == "train.slow_round" and e["detail"]["round"] == STALL_ROUND]
    before = [e for e in ring if e["kind"] == "span" and e["seq"] < at][-1]
    after = [e for e in ring if e["kind"] == "span" and e["seq"] > at][0]
    assert (before["name"], before["detail"]["round"]) == (
        "train.after_iteration", STALL_ROUND + 1)
    assert (after["name"], after["detail"]["next"]) == ("train.boundary", STALL_ROUND + 2)
    # and the account of that round agrees with the event
    acct = next(a for a in spans.round_account(0) if a["round"] == STALL_ROUND)
    assert acct["period_ns"] == ev["period_ns"] and _exact(acct)


def test_a_steady_run_and_the_rounds_that_compile_raise_nothing(sampler, lines):
    flight.clear()
    rng = np.random.default_rng(5)  # a shape no other test of this file has
    X = rng.normal(size=(331, 7)).astype(np.float32)
    d = xtb.DMatrix(X, label=(X[:, 1] > 0).astype(np.float32))
    xtb.train(dict(PARAMS, max_depth=3), d, 6, verbose_eval=False,
              callbacks=[Floor(floor_s=0.3)])
    assert _slow_events() == []
    assert not [line for line in lines if "slow_round" in line]
    accounts = spans.round_account(0)
    assert accounts[0]["warm"] and accounts[0]["compiled"] + accounts[0]["traced"] > 0
    # the round that compiled was longer than a steady one by more than the
    # threshold, and was not called slow
    assert accounts[0]["period_ns"] > 1.2 * accounts[-1]["period_ns"] + 20 * MS
    assert not accounts[-1]["warm"]
