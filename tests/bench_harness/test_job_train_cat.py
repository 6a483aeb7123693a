"""Job kind ``train-cat`` on the CPU at a size a test run can hold: a sound
run reads within every limit; the lower-precision control, the faults
planted in the reference's place and each fault planted in the program do
not; a program that cannot bin a categorical column past ``max_bin`` (the
parent commit's) ends the job before any data is made."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ROWS = 60_000
WORKLOAD = "airline-cat-d6.train"
COMPARED = {"bin_mass_gap", "bin_mismatch", "hess_gap", "grad_gap",
            "leaf_gap", "split_gap", "margin_gap", "catset_gap"}


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
    assert set(within) == COMPARED
    assert state.failed == 0 and state.attempted >= 1
    page = state.dtrain._ellpack
    assert page.bin_width == 300 and str(page.bins.dtype) == "int16"
    assert page.cuts.n_bins_array().tolist()[5:7] == [300, 300]


def test_the_control_in_bfloat16_is_not_correct(sound):
    _, numbers, _ = drive(lower_precision=True)
    limits = run.load_limits("train-cat")
    control = {k[:-4]: v for k, v in numbers.items() if k.endswith("_low")}
    assert set(control) == {"hess_gap", "grad_gap", "split_gap",
                            "catset_gap"}
    assert control["hess_gap"] > limits["hess_gap"]
    assert not all(r[3] for r in run.judge({**numbers, **control}, limits))


def test_every_fault_planted_in_the_reference_stands_over_a_limit(sound):
    _, numbers, _ = drive(faults=True)
    limits = run.load_limits("train-cat")
    for name, limit_of in (("hess_gap_flip", "hess_gap"),
                           ("grad_gap_flip", "grad_gap"),
                           ("catset_gap_nothreshold", "catset_gap"),
                           ("bin_mismatch_numeric", "bin_mismatch"),
                           ("bin_mass_gap_half", "bin_mass_gap"),
                           ("leaf_gap_altered", "leaf_gap"),
                           ("margin_gap_stale", "margin_gap")):
        assert numbers[name] > limits[limit_of], (name, numbers[name])


def test_a_program_that_caps_categories_at_max_bin_ends_before_any_data(
        monkeypatch):
    """The parent commit refused a categorical column of more categories
    than ``max_bin`` in its sketch: the job asks the program that question
    first, and ends with a message, no row made."""
    from xgboost_tpu.data import quantile

    real = quantile.sketch_dense

    def capped(X, max_bin, *a, cat_mask=None, **kw):
        if cat_mask is not None and np.nanmax(np.asarray(X)) + 1 > max_bin:
            raise ValueError("categorical feature 0 has too many "
                             "categories; raise max_bin")
        return real(X, max_bin, *a, cat_mask=cat_mask, **kw)

    monkeypatch.setattr(quantile, "sketch_dense", capped)
    cell = run.load_cell(WORKLOAD)
    job = run.load_module("jobs", cell["traffic"]["job"])

    def no_rows(*a, **kw):
        raise AssertionError("the job made data on a program that cannot "
                             "run the cell")

    monkeypatch.setitem(job.MAKERS, cell["config"]["dataset"]["maker"],
                        no_rows)
    with pytest.raises(SystemExit, match="more categories than max_bin"):
        job.setup(cell, 1, {"log": lambda s: None, "rehearse_rows": ROWS})


# ------------------------------------------- faults planted in the program
def category_flipped_in_the_route(monkeypatch, state=None):
    """The route moves one category of every categorical split's set (its
    first) to the other side; the scan's sums are the sound ones."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import grow

    real = grow._update_positions

    def route(bins, pos, best, *a):
        first = jnp.argmax(best.cat_set, axis=1)
        rows = jnp.arange(best.cat_set.shape[0])
        flipped = best.cat_set.at[rows, first].set(~best.cat_set[rows, first])
        return real(bins, pos, best._replace(cat_set=flipped), *a)

    monkeypatch.setattr(grow, "_update_positions", route)


def threshold_ignored(monkeypatch, state=None):
    """The scan is told no ``max_cat_threshold``: a partition may gather
    any number of categories."""
    if state is not None:
        state.params["max_cat_threshold"] = 1 << 30


def codes_binned_as_numbers(monkeypatch, state=None):
    """The sketch treats the categorical columns as numeric ones: their
    codes are binned through quantile cuts of ``max_bin`` bins."""
    from xgboost_tpu.data import dmatrix

    real = dmatrix.sketch_dense

    def sketch(X, max_bin, *a, cat_mask=None, **kw):
        return real(X, max_bin, *a, **kw)

    monkeypatch.setattr(dmatrix, "sketch_dense", sketch)


def altered_leaf(monkeypatch, state=None):
    """One answer altered where it is produced: the first leaf of every
    tree, by 1%."""
    from xgboost_tpu.tree.grow import HistTreeGrower

    if state is not None:
        return  # patched once, before the set-up

    real = HistTreeGrower.to_host

    def to_host(grown):
        tree = real(grown)
        leaf_val = np.array(tree.leaf_val)
        leaf_val[np.flatnonzero(tree.is_leaf)[0]] *= 1.01
        return tree._replace(leaf_val=leaf_val)

    monkeypatch.setattr(HistTreeGrower, "to_host", staticmethod(to_host))


def window_margin_left_out(monkeypatch, state=None):
    """The margin update of every round past the warm ones left out, the
    last tree's among them: the trees the reference follows and scans are
    grown from the sound margin."""
    import jax.numpy as jnp

    import xgboost_tpu.core as core

    if state is None:
        return
    real, calls = core.leaf_margin_delta, []
    warm = int(state.cell["traffic"]["warm_rounds"])

    def delta(pos, leaf_val):
        calls.append(1)
        if len(calls) <= warm:
            return real(pos, leaf_val)
        return jnp.zeros(pos.shape, jnp.float32)

    monkeypatch.setattr(core, "leaf_margin_delta", delta)


@pytest.mark.parametrize("fault,caught_by", [
    (category_flipped_in_the_route, {"hess_gap", "grad_gap"}),
    (threshold_ignored, {"catset_gap"}),
    (codes_binned_as_numbers, {"bin_mismatch"}),
    (altered_leaf, {"leaf_gap", "margin_gap"}),
    (window_margin_left_out, {"margin_gap"}),
], ids=lambda f: getattr(f, "__name__", None))
def test_a_broken_categorical_path_is_not_correct(sound, monkeypatch,
                                                  retraced, fault, caught_by):
    fault(monkeypatch)
    _, numbers, within = drive(
        before_window=lambda state: fault(monkeypatch, state))
    assert not all(within.values())
    over = {k for k, ok in within.items() if not ok}
    assert caught_by <= over, (numbers, over)
