"""Job kind ``train`` on the CPU at a size a test run can hold: a sound run
reads within the limits, the lower-precision control and each fault a
training cell can have do not, and the window's rate is rows x rounds over
the whole wall time.  The harness's look for a chip is skipped: the job's
own ``setup``, ``window`` and ``compare`` are driven as ``run_cell`` drives
them, with the timed path broken underneath."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ROWS = 6000
WORKLOAD = "higgs-d6.train"


def drive(seed=7, seconds=0.05, **compare_kw):
    cell = run.load_cell(WORKLOAD)
    job = run.load_module("jobs", cell["traffic"]["job"])
    env = {"log": lambda s: None, "rehearse_rows": ROWS}
    state = job.setup(cell, seed, env)
    job.window(state, seconds)
    numbers = job.compare(state, env, **compare_kw)
    rows = run.judge(numbers, run.load_limits(cell["traffic"]["job"]))
    return state, numbers, {r[0]: r[3] for r in rows}


@pytest.fixture(scope="module")
def sound():
    return drive()


def test_a_sound_run_reads_within_every_limit(sound):
    state, numbers, within = sound
    assert all(within.values()), (numbers, within)
    assert state.failed == 0 and state.attempted >= 1


def test_rate_is_rows_times_rounds_over_the_whole_wall_time(sound, monkeypatch):
    import xgboost_tpu.core as core

    real = core.Booster.update
    calls = []

    def stalling(self, dtrain, iteration, fobj=None):
        calls.append(iteration)
        if iteration == 3:
            time.sleep(0.4)  # one round of the window stalls
        return real(self, dtrain, iteration, fobj)

    monkeypatch.setattr(core.Booster, "update", stalling)
    state, _, _ = drive(seconds=0.6)
    c = state.clocks
    assert c["round_max_s"] >= 0.4
    assert c["window_s"] >= sum(c["round_s"]) - 1e-6 >= 0.4
    assert c["row_rounds"] == ROWS * state.attempted == ROWS * len(c["round_s"])
    ctx = {"clocks": c}
    rate = run.load_module("metrics", "train_rate").read(ctx)
    assert rate == pytest.approx(ROWS * state.attempted / c["window_s"] / 1e6)
    # a rate over the rounds' own time alone would hide the stall
    assert rate < ROWS * state.attempted / (c["window_s"] - 0.4) / 1e6


def test_the_control_in_bfloat16_is_not_correct(sound):
    _, numbers, _ = drive(lower_precision=True)
    limits = run.load_limits("train")
    control = {k[:-4]: v for k, v in numbers.items() if k.endswith("_low")}
    assert set(control) == {"hess_gap", "grad_gap", "split_gap"}
    assert control["hess_gap"] > 3 * limits["hess_gap"]
    assert control["grad_gap"] > 3 * limits["grad_gap"]
    assert not all(r[3] for r in run.judge({**numbers, **control}, limits))


def no_margin_update(monkeypatch):
    """A step that returns its state unchanged: the margin never moves."""
    import jax.numpy as jnp

    import xgboost_tpu.core as core

    monkeypatch.setattr(core, "leaf_margin_delta",
                        lambda pos, leaf_val: jnp.zeros(pos.shape, jnp.float32))


def half_of_the_batch(monkeypatch):
    """Half of the rows left out of every tree, the rest counted double."""
    import jax.numpy as jnp

    from xgboost_tpu.tree.grow import HistTreeGrower

    real = HistTreeGrower.grow

    def grow(self, bins, gpair, valid, *a, **kw):
        keep = (jnp.arange(gpair.shape[0]) % 2 == 0)[:, None]
        return real(self, bins, jnp.where(keep, 2.0 * gpair, 0.0), valid, *a, **kw)

    monkeypatch.setattr(HistTreeGrower, "grow", grow)


def altered_leaf(monkeypatch):
    """One answer altered where it is produced: a leaf's value, by 1%."""
    from xgboost_tpu.tree.grow import HistTreeGrower

    real = HistTreeGrower.to_host

    def to_host(state):
        tree = real(state)
        leaf_val = np.array(tree.leaf_val)
        leaf_val[np.flatnonzero(tree.is_leaf)[0]] *= 1.01
        return tree._replace(leaf_val=leaf_val)

    monkeypatch.setattr(HistTreeGrower, "to_host", staticmethod(to_host))


def altered_split(monkeypatch):
    """One answer altered where it is produced: the root's cut, moved."""
    from xgboost_tpu.tree.grow import HistTreeGrower

    real = HistTreeGrower.to_host

    def to_host(state):
        tree = real(state)
        thr = np.array(tree.thr)
        thr[0] += 0.25
        return tree._replace(thr=thr)

    monkeypatch.setattr(HistTreeGrower, "to_host", staticmethod(to_host))


@pytest.mark.parametrize("fault,caught_by", [
    (no_margin_update, {"hess_gap", "grad_gap", "margin_gap"}),
    (half_of_the_batch, {"hess_gap", "grad_gap"}),
    (altered_leaf, {"leaf_gap", "margin_gap"}),
    (altered_split, {"hess_gap", "grad_gap"}),
], ids=lambda f: getattr(f, "__name__", None))
def test_a_broken_timed_path_is_not_correct(sound, monkeypatch, fault, caught_by):
    fault(monkeypatch)
    _, numbers, within = drive()
    assert not all(within.values())
    over = {k for k, ok in within.items() if not ok}
    assert caught_by <= over, (numbers, over)


def test_holes_are_made_as_the_traffic_file_lists_them_and_the_run_keeps_the_blocks(sound):
    state, _, _ = sound
    sizes = state.cell["traffic"]["allocator_holes"]
    assert all(size >= 256 and count >= 1 for size, count in sizes)
    # one kept block before the first hole and one after each
    assert len(state.kept) == 1 + sum(count for _, count in sizes)
    job = run.load_module("jobs", state.cell["traffic"]["job"])
    said = []
    kept = job.make_holes([[512, 3], [4096, 2]], said.append)
    assert len(kept) == 6 and all(k.nbytes == 256 for k in kept)
    assert "5 holes of 9728 bytes" in said[0]
    assert job.make_holes([], said.append) != []  # no hole asked: one block, no fault
