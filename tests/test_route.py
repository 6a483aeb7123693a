"""The position rewrite has two forms (tree/grow.py): per-row gathers beside
the CPU's row-pass histogram, one dense pass beside the one-hot matmul the
chip runs.  It is integer logic, so the two are held bitwise equal, on inputs
that reach every branch; both are called by function, whatever the backend
would have picked."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.tree import grow

R, F, B = 5003, 7, 16  # a row count no chunk or tile divides


def level(width, depth, seed, has_cat, B=B):
    """Rows and node tables of the level at ``depth``, padded to ``width``
    slots: whatever the slots past ``2**depth`` hold must reach no row."""
    rng = np.random.default_rng(seed)
    node0, real = (1 << depth) - 1, 1 << depth
    bins = rng.integers(0, B, size=(R, F))
    bins[rng.random((R, F)) < 0.1] = B                   # missing
    pos = node0 + rng.integers(0, real, size=R)          # on the level
    where = rng.random(R)
    above = rng.integers(0, max(node0, 1), size=R) if node0 else -1
    pos = np.where(where < 0.15, above, pos)             # a leaf further up
    pos = np.where(where < 0.05, -1, pos)                # padded rows
    can = rng.random(width) < 0.7
    can[real:] = rng.random(width - real) < 0.5          # garbage slots
    dleft = rng.random(width) < 0.5
    if depth >= 2:                                       # each branch, whatever the seed
        can[:3], dleft[:3] = (True, False, True), (True, True, False)
    feature = rng.integers(0, F, size=width)
    feature[~can] = -1                                   # as evaluate_splits leaves it
    best = types.SimpleNamespace(
        feature=jnp.asarray(feature, jnp.int32),
        bin=jnp.asarray(rng.integers(-1, B + 1, size=width), jnp.int32),
        default_left=jnp.asarray(dleft),
        is_cat=jnp.asarray(rng.random(width) < (0.4 if has_cat else 0.0)),
        cat_set=jnp.asarray(rng.random((width, B)) < 0.5))
    return (jnp.asarray(bins, jnp.int16), jnp.asarray(pos, jnp.int32), best,
            jnp.asarray(can), node0)


@pytest.mark.parametrize("has_cat,n_bin", [(False, B), (True, B),
                                           (True, 300)],
                         ids=["num", "cat", "cat-300-bins"])
@pytest.mark.parametrize("width,depth,traced", [
    (1, 0, False),                     # the root's program
    (32, 5, False), (32, 5, True),     # a full level, as level_step has it
    (32, 2, False), (32, 2, True),     # 28 garbage slots, as level_step_padded
    (128, 7, False), (128, 7, True),
    (128, 4, True),
])
def test_dense_form_equals_gather_form(width, depth, traced, has_cat, n_bin):
    """Categorical sets of 300 bins (a column past ``max_bin``: the page's
    width and sentinel follow it) and of more than 32 nodes (the membership
    in several words a bin) route alike in both forms."""
    bins, pos, best, can, node0 = level(width, depth, width + depth, has_cat,
                                        n_bin)

    def run(form):
        def call(node0):
            return form(bins, pos, best, can, node0, width, n_bin, has_cat)
        return np.asarray(jax.jit(call)(jnp.int32(node0)) if traced
                          else call(node0))

    want = run(grow._update_positions_gather)
    got = run(grow._update_positions_dense)
    np.testing.assert_array_equal(got, want)

    if not depth:
        return
    # and the inputs did reach every branch
    p, b = np.asarray(pos), np.asarray(bins)
    on = (p >= node0) & (p < node0 + (1 << depth))
    moved = want != p
    assert moved.any() and (~moved & on).any()           # can_split both ways
    assert not moved[~on].any() and (p == -1).any()
    assert set(np.unique(want[moved] - 2 * p[moved])) == {1, 2}
    feat = np.clip(np.asarray(best.feature), 0, F - 1)[np.clip(p - node0, 0, width - 1)]
    miss = moved & (b[np.arange(R), feat] == n_bin)
    assert set(np.unique(want[miss] - 2 * p[miss])) == {1, 2}  # default both ways


@pytest.mark.parametrize("hist,columns,n_bin,form", [
    ("matmul", F, B, "dense"),
    ("scatter", F, B, "gather"),
    ("matmul", 4096, 1 << 15, "dense"),     # 3 + 16 + 12 bits: the last fit
    ("matmul", 4097, 1 << 15, "gather"),    # a node's entry passes one int32
])
def test_the_backend_picks_the_form(monkeypatch, hist, columns, n_bin, form):
    """``_update_positions`` follows the histogram: dense beside the matmul,
    gathers beside the row pass, and wherever the packed entry cannot fit."""
    _, pos, best, can, node0 = level(32, 3, 0, False)
    called = []
    for name in ("gather", "dense"):
        monkeypatch.setattr(grow, "_update_positions_" + name,
                            lambda *a, _n=name: called.append(_n) or a[1])
    monkeypatch.setenv("XTB_HIST_IMPL", hist)
    grow._update_positions(jnp.zeros((8, columns), jnp.int32), pos, best, can,
                           node0, 32, n_bin, False)
    assert called == [form]


def test_the_widest_entry_that_fits_is_exact():
    """At the last width that packs, the feature's top bit sits on bit 30."""
    width, columns, n_bin, rows = 4, 4096, 1 << 15, 64
    rng = np.random.default_rng(0)
    bins = rng.integers(0, n_bin + 1, size=(rows, columns)).astype(np.int32)
    best = types.SimpleNamespace(
        feature=jnp.asarray([columns - 1, 0, 2048, 4095], jnp.int32),
        bin=jnp.asarray([n_bin - 1, 0, n_bin // 2, n_bin - 2], jnp.int32),
        default_left=jnp.asarray([True, False, True, False]))
    pos = jnp.asarray(3 + rng.integers(0, width, size=rows), jnp.int32)
    args = (jnp.asarray(bins), pos, best, jnp.ones(width, bool), 3, width,
            n_bin, False)
    np.testing.assert_array_equal(
        np.asarray(grow._update_positions_dense(*args)),
        np.asarray(grow._update_positions_gather(*args)))
