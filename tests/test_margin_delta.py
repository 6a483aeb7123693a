"""The margin update has two forms (tree/grow.py): a row-sized gather of the
leaf values beside the CPU's row-pass histogram or past a slot bound, one
dense select over the tree's slots otherwise.  The select moves bits, so the
two are held bitwise equal as int32 views, on leaf values that hold -0.0,
NaN, the infinities and denormals; both are called by function, whatever the
backend would have picked."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.tree import grow

R = 5003  # a row count no tile divides

SPECIAL = np.array([-0.0, np.nan, -np.nan, np.inf, -np.inf,
                    1e-45, -1e-45, 1e-39, -3e-40, 0.0], np.float32)


def leaves(S, seed):
    """``S`` leaf values, the special ones first where they fit, and a NaN
    whose payload is not the default one."""
    v = np.random.default_rng(seed).normal(size=S).astype(np.float32)
    n = min(S, len(SPECIAL))
    v[:n] = SPECIAL[:n]
    if S > n:
        v[n:n + 1] = np.array([0x7fc12345], np.int32).view(np.float32)
    return v


def positions(S, seed):
    """Every slot hit, one row in ten padded (-1), the rest anywhere."""
    rng = np.random.default_rng(seed + 1)
    pos = rng.integers(0, S, size=R)
    pos[rng.random(R) < 0.1] = -1
    pos[rng.permutation(R)[:S]] = np.arange(S)
    return pos.astype(np.int32)


def bits(x):
    return np.asarray(x).view(np.int32)


@pytest.mark.parametrize("traced", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("S", [3, 127, 510, 511])
def test_dense_form_equals_gather_form(S, traced):
    pos, val = positions(S, S), leaves(S, S)

    def run(form):
        call = jax.jit(form) if traced else form
        return bits(call(jnp.asarray(pos), jnp.asarray(val)))

    want = run(grow._margin_delta_gather)
    got = run(grow._margin_delta_dense)
    np.testing.assert_array_equal(got, want)
    # the rows read what the tree holds: their own leaf's bits, +0.0 padded
    np.testing.assert_array_equal(
        want, np.where(pos >= 0, bits(val)[np.clip(pos, 0, S - 1)], 0))
    assert set(np.unique(pos)) == {-1, *range(S)}


def test_every_special_value_reaches_a_row():
    """At 127 slots every special value and the NaN payload stand in a
    slot that rows sit on, and come out of the dense form unchanged."""
    pos, val = positions(127, 0), leaves(127, 0)
    got = bits(grow._margin_delta_dense(jnp.asarray(pos), jnp.asarray(val)))
    for s in range(len(SPECIAL) + 1):
        assert (got[pos == s] == bits(val)[s]).all()
    assert (got[pos == -1] == 0).all()


@pytest.mark.parametrize("hist,slots,form", [
    ("matmul", 127, "dense"),
    ("matmul", 511, "dense"),
    ("matmul", grow._MARGIN_DENSE_SLOTS, "dense"),      # the widest dense
    ("matmul", grow._MARGIN_DENSE_SLOTS + 1, "gather"),
    ("scatter", 127, "gather"),                         # beside the row pass
    ("native", 127, "gather"),
])
def test_the_form_follows_the_histogram_and_the_slot_bound(
        monkeypatch, hist, slots, form):
    monkeypatch.setenv("XTB_HIST_IMPL", hist)
    assert grow.margin_form(slots) == form
    called = []
    for name in ("gather", "dense"):
        monkeypatch.setattr(grow, "_margin_delta_" + name,
                            lambda p, v, _n=name: called.append(_n) or p)
    grow.leaf_margin_delta.__wrapped__(jnp.zeros(8, jnp.int32),
                                       jnp.zeros(slots, jnp.float32))
    assert called == [form]


@pytest.mark.parametrize("hist,form", [("matmul", "dense"),
                                       ("scatter", "gather")])
def test_the_margin_span_names_its_form_and_slots(monkeypatch, hist, form):
    """A depth-3 train: 15 slots a tree, each ``grow.margin`` span says
    which form updated the margin."""
    import xgboost_tpu as xtb
    from xgboost_tpu.telemetry import flight, spans

    monkeypatch.setenv("XTB_HIST_IMPL", hist)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    flight.clear()
    xtb.train({"objective": "binary:logistic", "max_depth": 3},
              xtb.DMatrix(X, label=y), 2)
    got = spans.recent("grow.margin")
    assert len(got) == 2
    assert {(r["margin.form"], r["margin.slots"]) for r in got} == {(form, 15)}
