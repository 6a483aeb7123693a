"""Job kind ``train-bestfirst`` on the CPU at a size a test run can hold: a
sound run reads within the limits; the lower-precision control and each fault
a best-first grower can have, planted in the program underneath the timed
call, do not; the faults the reference plants in its own place read over the
limits too.  The harness's look for a chip is skipped: the job's own
``setup``, ``window`` and ``compare`` are driven as ``run_cell`` drives
them.  The budget is cut to 24 leaves so that it binds at this size."""
import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ROWS = 24000
LEAVES = 24
WORKLOAD = "higgs-leafwise-255.train"
JOB = "train-bestfirst"


def drive(seed=7, seconds=0.05, params=None, **compare_kw):
    cell = copy.deepcopy(run.load_cell(WORKLOAD))
    cell["config"]["params"]["max_leaves"] = LEAVES
    cell["config"]["guarantees"]["max_leaves"] = LEAVES
    job = run.load_module("jobs", cell["traffic"]["job"])
    env = {"log": lambda s: None, "rehearse_rows": ROWS}
    state = job.setup(cell, seed, env)
    state.params.update(params or {})  # the program's, not the configuration's
    job.window(state, seconds)
    numbers = job.compare(state, env, **compare_kw)
    rows = run.judge(numbers, run.load_limits(JOB))
    return state, numbers, {r[0]: r[3] for r in rows}


@pytest.fixture(scope="module")
def sound():
    return drive()


def test_the_cell_names_the_job_and_a_sound_run_reads_within_every_limit(sound):
    state, numbers, within = sound
    assert state.cell["traffic"]["job"] == JOB
    assert set(within) == {"bin_mass_gap", "bin_mismatch", "hess_gap",
                           "grad_gap", "leaf_gap", "split_gap", "order_gap",
                           "leaves_gap", "child_hess_gap", "margin_gap"}
    assert all(within.values()), (numbers, within)
    assert state.failed == 0 and state.attempted >= 1
    assert all(t.num_leaves == LEAVES for t in state.bst.trees)
    assert max(t.max_depth for t in state.bst.trees) > 5  # no level loop's


def test_the_control_in_bfloat16_is_not_correct(sound):
    _, numbers, _ = drive(lower_precision=True)
    limits = run.load_limits(JOB)
    control = {k[:-4]: v for k, v in numbers.items() if k.endswith("_low")}
    assert set(control) == {"hess_gap", "grad_gap", "split_gap", "order_gap"}
    assert control["hess_gap"] > 3 * limits["hess_gap"]
    assert control["grad_gap"] > 3 * limits["grad_gap"]
    assert not all(r[3] for r in run.judge({**numbers, **control}, limits))


def test_the_faults_planted_in_the_references_place_read_over_the_limits(sound):
    _, numbers, _ = drive(faults=True)
    limits = run.load_limits(JOB)
    for name in ("order_gap_by_id", "order_gap_topk", "order_gap_nosub"):
        assert numbers[name] > limits["order_gap"], (name, numbers[name])
    for name in ("leaves_gap_short", "leaves_gap_over"):
        assert numbers[name] > limits["leaves_gap"]
    for name in ("hess_gap_half", "hess_gap_stale", "grad_gap_half",
                 "grad_gap_stale"):
        assert numbers[name] > limits[name[:8]], (name, numbers[name])
    assert numbers["bin_mass_gap_half"] > limits["bin_mass_gap"]
    assert numbers["margin_gap_stale"] > limits["margin_gap"]
    assert numbers["child_hess_gap_moved"] > 3 * limits["child_hess_gap"]


# ---- faults planted in the program, under the timed call ------------------
def pair_in_bfloat16(monkeypatch):
    import jax.numpy as jnp

    from xgboost_tpu.tree.bestfirst import BestFirstGrower

    real = BestFirstGrower.grow

    def grow(self, bins, gpair, *a, **kw):
        low = gpair.astype(jnp.bfloat16).astype(jnp.float32)
        return real(self, bins, low, *a, **kw)

    monkeypatch.setattr(BestFirstGrower, "grow", grow)


def stale_candidates(monkeypatch):
    """Every tree grown on the gradient of the round before."""
    from xgboost_tpu.tree.bestfirst import BestFirstGrower

    real, seen = BestFirstGrower.grow, []

    def grow(self, bins, gpair, *a, **kw):
        seen.append(gpair)
        return real(self, bins, seen[-2] if len(seen) > 1 else gpair, *a, **kw)

    monkeypatch.setattr(BestFirstGrower, "grow", grow)


def top_k_commit(monkeypatch):
    """The replay commits every evaluated open leaf, best first, and waits
    for none whose children are not known: splits out of pop order."""
    import jax.numpy as jnp
    from jax import lax

    from xgboost_tpu.tree import bestfirst

    def replay(st, *, max_leaves, gamma_eps):
        def step(carry):
            fid, split, n, _, _ = carry
            is_open = (fid >= 0) & ~split
            every = jnp.max(jnp.where(is_open, st.cand_gain, -jnp.inf))
            gain = jnp.where(is_open & (st.left >= 0), st.cand_gain, -jnp.inf)
            nid = jnp.argmax(gain)
            done = (every <= gamma_eps) | (n >= max_leaves - 1)
            commit = ~done & (gain[nid] > gamma_eps)
            kid = jnp.where(commit, 2 * n + 1, -1)
            return (fid.at[jnp.where(commit, st.left[nid], nid)]
                    .set(jnp.where(commit, kid, fid[nid]))
                    .at[jnp.where(commit, st.right[nid], nid)]
                    .set(jnp.where(commit, kid + 1, fid[nid])),
                    split.at[nid].set(split[nid] | commit),
                    n + commit.astype(jnp.int32), ~commit, done)

        fid, split, n, _, done = lax.while_loop(
            lambda c: ~c[3], step,
            (st.fid, st.split, st.n_splits, jnp.zeros((), bool),
             jnp.zeros((), bool)))
        return st._replace(
            fid=fid, split=split, n_splits=n, done=done,
            told=jnp.stack([done.astype(jnp.int32), n, st.n_alloc]))

    monkeypatch.setattr(bestfirst, "_replay", replay)
    _recompile(monkeypatch, bestfirst)


def no_subtraction_for_one_pair(monkeypatch):
    """Where a pass holds one pair alone (the root's children first), the
    derived sibling's histogram stays zero."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import bestfirst

    real = bestfirst.combine_sibling_hists

    def combine(built, parents, alive):
        hist = real(built, parents, alive)
        return hist.at[1].multiply(
            jnp.where(jnp.sum(alive) == 2, 0.0, 1.0))

    monkeypatch.setattr(bestfirst, "combine_sibling_hists", combine)
    _recompile(monkeypatch, bestfirst)


def _recompile(monkeypatch, bestfirst):
    """A pass traced anew, so that it sees what was patched under it: a
    fresh function, because a trace is cached by the function traced."""
    import jax

    real = bestfirst.level_step_bestfirst.__wrapped__

    def level_step_bestfirst(*args, **kw):
        return real(*args, **kw)

    monkeypatch.setattr(
        bestfirst, "level_step_bestfirst",
        jax.jit(level_step_bestfirst, static_argnames=bestfirst._STATIC))


@pytest.mark.parametrize("fault,params,caught_by", [
    (pair_in_bfloat16, {}, {"hess_gap", "grad_gap"}),
    (stale_candidates, {}, {"hess_gap", "grad_gap"}),
    (top_k_commit, {}, {"order_gap"}),
    (no_subtraction_for_one_pair, {}, {"order_gap"}),
    # the same budget spent in node-id order: the level loop under a budget
    (None, {"grow_policy": "depthwise", "max_depth": 8}, {"order_gap"}),
    (None, {"max_leaves": LEAVES - 1}, {"leaves_gap", "order_gap"}),
    (None, {"max_leaves": LEAVES + 1}, {"leaves_gap"}),
    # min_child_weight binds at this size: a grower that ignores it
    (None, {"min_child_weight": 1}, {"child_hess_gap"}),
], ids=["bfloat16", "stale", "top-k-commit", "no-subtraction", "by-node-id",
        "budget-short", "budget-over", "min-child-weight-ignored"])
def test_a_broken_timed_path_is_not_correct(sound, monkeypatch, fault, params,
                                            caught_by):
    if fault:
        fault(monkeypatch)
    _, numbers, within = drive(params=params)
    over = {k for k, ok in within.items() if not ok}
    assert caught_by <= over, (numbers, over)


def test_a_program_without_the_pass_ends_at_once(monkeypatch):
    from xgboost_tpu.tree import bestfirst

    monkeypatch.delattr(bestfirst, "level_step_bestfirst")
    cell = run.load_cell(WORKLOAD)
    job = run.load_module("jobs", JOB)
    with pytest.raises(SystemExit, match="level_step_bestfirst"):
        job.setup(cell, 1, {"log": lambda s: None, "rehearse_rows": 1000})
