"""Job kind ``train-missing`` on the CPU at a size a test run can hold: a
sound run reads within every limit, the lower-precision control and each
fault the missing path can have do not.  The faults are planted in the
program, underneath the job's own ``setup``, ``window`` and ``compare``,
driven as ``run_cell`` drives them."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ROWS = 24_000
WORKLOAD = "bosch-d8.train"


def drive(seed=7, seconds=0.05, before_window=None, **compare_kw):
    cell = run.load_cell(WORKLOAD)
    job = run.load_module("jobs", cell["traffic"]["job"])
    env = {"log": lambda s: None, "rehearse_rows": ROWS}
    state = job.setup(cell, seed, env)
    if before_window:
        before_window(state)
    job.window(state, seconds)
    numbers = job.compare(state, env, **compare_kw)
    rows = run.judge(numbers, run.load_limits(cell["traffic"]["job"]))
    return state, numbers, {r[0]: r[3] for r in rows}


@pytest.fixture(scope="module")
def sound():
    return drive()


@pytest.fixture
def retraced():
    """A fault planted inside a jitted level step is seen only by a fresh
    trace: the programs traced before and with it are dropped."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def test_a_sound_run_reads_within_every_limit(sound):
    state, numbers, within = sound
    assert all(within.values()), (numbers, within)
    assert set(within) == {"bin_mass_gap", "bin_mismatch", "hess_gap",
                           "grad_gap", "leaf_gap", "split_gap", "margin_gap",
                           "default_gap"}
    assert state.failed == 0 and state.attempted >= 1
    assert state.params["scale_pos_weight"] == ROWS / np.count_nonzero(state.y)
    assert 0.79 < np.isnan(state.X).mean() < 0.83


def test_the_control_in_bfloat16_is_not_correct(sound):
    _, numbers, _ = drive(lower_precision=True)
    limits = run.load_limits("train-missing")
    control = {k[:-4]: v for k, v in numbers.items() if k.endswith("_low")}
    assert set(control) == {"hess_gap", "grad_gap", "split_gap"}
    assert control["hess_gap"] > limits["hess_gap"]
    assert control["grad_gap"] > limits["grad_gap"]
    assert not all(r[3] for r in run.judge({**numbers, **control}, limits))


def test_every_fault_planted_in_the_reference_stands_over_a_limit(sound):
    _, numbers, _ = drive(faults=True)
    limits = run.load_limits("train-missing")
    for tag, over in (("right", {"hess_gap", "grad_gap", "default_gap"}),
                      ("left", {"hess_gap", "grad_gap", "default_gap"}),
                      ("nototal", {"hess_gap", "grad_gap", "split_gap"}),
                      ("nan0", {"bin_mismatch"}),
                      ("nodefault", {"margin_gap"}),
                      ("nospw", {"hess_gap", "grad_gap"}),
                      ("moved", {"split_gap"}), ("half", {"bin_mass_gap"})):
        got = {k[:-len(tag) - 1]: v for k, v in numbers.items()
               if k.endswith("_" + tag)}
        assert over <= set(got), (tag, got)
        for name in over:
            # three times over, but for the halved sketch: its measure reads
            # 1.1 to 1.3 and the limit stands in the middle of its readings
            room = 1.5 if tag == "half" else 3
            assert got[name] > room * limits[name] and got[name] > limits[name], (
                tag, name, got[name])


# ------------------------------------------- faults planted in the program
def absent_sent(way: bool):
    def fault(monkeypatch, state=None):
        """The route sends every absent entry one way, whatever the split
        learned."""
        import jax.numpy as jnp

        from xgboost_tpu.tree import grow

        real = grow._update_positions

        def route(bins, pos, best, *a):
            fixed = jnp.full_like(best.default_left, way)
            return real(bins, pos, best._replace(default_left=fixed), *a)

        monkeypatch.setattr(grow, "_update_positions", route)
    fault.__name__ = "absent_sent_" + ("left" if way else "right")
    return fault


def direction_not_learned(monkeypatch, state=None):
    """The scan scores one direction: every absent row right, and says so."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import grow

    real = grow.evaluate_splits

    def scan(hist, totals, *a, **kw):
        # with the absent rows' sums counted into the last valid bin's mass
        # nothing is left to send left: one candidate a cut
        best = real(hist, totals, *a, **kw)
        feat_sum = jnp.take_along_axis(
            hist.sum(axis=2), best.feature[:, None, None], axis=1)[:, 0]
        miss = totals - feat_sum
        moved = best.default_left[:, None]
        return best._replace(
            default_left=jnp.zeros_like(best.default_left),
            left_sum=jnp.where(moved, best.left_sum - miss, best.left_sum),
            right_sum=jnp.where(moved, best.right_sum + miss, best.right_sum))

    monkeypatch.setattr(grow, "evaluate_splits", scan)


def absent_rows_out_of_the_totals(monkeypatch, state=None):
    """The children's totals leave out the rows whose entry is absent."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import grow

    real = grow.evaluate_splits

    def scan(hist, totals, *a, **kw):
        best = real(hist, totals, *a, **kw)
        feat_sum = jnp.take_along_axis(
            hist.sum(axis=2), best.feature[:, None, None], axis=1)[:, 0]
        miss = totals - feat_sum
        went_left = best.default_left[:, None]
        return best._replace(
            left_sum=jnp.where(went_left, best.left_sum - miss, best.left_sum),
            right_sum=jnp.where(went_left, best.right_sum,
                                best.right_sum - miss))

    monkeypatch.setattr(grow, "evaluate_splits", scan)


def nan_binned_into_bin_0(monkeypatch, state=None):
    import jax.numpy as jnp

    from xgboost_tpu.data import dmatrix

    real = dmatrix.build_ellpack

    def build(*a, **kw):
        page = real(*a, **kw)
        return page.__class__(**{**page.__dict__, "bins": jnp.where(
            page.bins == page.bin_width, 0, page.bins).astype(page.bins.dtype)})

    monkeypatch.setattr(dmatrix, "build_ellpack", build)


def margin_update_ignores_default_left(monkeypatch, state=None):
    """The tree is grown and recorded soundly; the rows' leaves that the
    margin update reads are found again with every absent entry sent right."""
    import jax.numpy as jnp

    from xgboost_tpu.tree.grow import HistTreeGrower

    real = HistTreeGrower.grow

    def grow(self, bins, gpair, valid, cuts_pad, *a, **kw):
        st = real(self, bins, gpair, valid, cuts_pad, *a, **kw)
        B = cuts_pad.shape[1]
        pos = jnp.where(valid, 0, -1)
        for _ in range(self.max_depth):
            at = jnp.clip(pos, 0)
            f = st.feat[at]
            entry = jnp.take_along_axis(
                bins, jnp.clip(f, 0)[:, None].astype(jnp.int32), axis=1)[:, 0]
            left = (entry.astype(jnp.int32) <= st.sbin[at]) & (entry < B)
            child = 2 * pos + 1 + jnp.where(left, 0, 1)
            pos = jnp.where((pos >= 0) & (f >= 0) & ~st.is_leaf[at], child, pos)
        return st._replace(pos=pos)

    monkeypatch.setattr(HistTreeGrower, "grow", grow)


def scale_pos_weight_left_out(monkeypatch, state=None):
    if state is not None:
        state.params.pop("scale_pos_weight")


@pytest.mark.parametrize("fault,caught_by", [
    (absent_sent(False), {"hess_gap", "grad_gap"}),
    (absent_sent(True), {"hess_gap", "grad_gap"}),
    (direction_not_learned, {"default_gap"}),
    (absent_rows_out_of_the_totals, {"hess_gap", "grad_gap"}),
    (nan_binned_into_bin_0, {"bin_mismatch"}),
    (margin_update_ignores_default_left, {"margin_gap"}),
    (scale_pos_weight_left_out, {"hess_gap", "grad_gap"}),
], ids=lambda f: getattr(f, "__name__", None))
def test_a_broken_missing_path_is_not_correct(sound, monkeypatch, retraced,
                                              fault, caught_by):
    fault(monkeypatch)
    _, numbers, within = drive(
        before_window=lambda state: fault(monkeypatch, state))
    assert not all(within.values())
    over = {k for k, ok in within.items() if not ok}
    assert caught_by <= over, (numbers, over)
