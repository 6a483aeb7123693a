"""Vectorized tree-traversal prediction.

TPU-native equivalent of the reference's GPU predictor
(src/predictor/gpu_predictor.cu:203 PredictKernel — one CUDA thread per row).
Here the whole row batch advances one tree level per step (rows at leaves
stick), a ``lax.scan`` walks trees, and the per-row feature read is a
``take_along_axis`` gather.  Raw feature values + thresholds are used (not
bins) so the same code serves training-eval and inference on fresh data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# --------------------------------------------------------------- bucket cache
# Shared shape-bucket policy for every margin-predict caller (training eval,
# Booster.predict, the serving engine).  jax.jit specializes per shape, so
# without bucketing each distinct row count compiles a fresh program; with it,
# steady-state traffic lands on a handful of padded shapes that all hit the
# same jit cache (the role of the reference GPU predictor's fixed thread-block
# geometry, gpu_predictor.cu).  Rows are padded with NaN — traversal is
# row-independent, so the pad rows change nothing and are sliced off.

_MIN_ROW_BUCKET = 8
# past this, pow2 padding could waste up to 2x; fall back to chunk multiples
_POW2_ROW_CEILING = 4096


def round_up_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def bucket_rows(n: int) -> int:
    """Padded row count for a batch of ``n`` rows: power-of-two buckets up to
    4096, then multiples of 4096 (bounded <0.1% overhead at scale)."""
    n = int(n)
    if n <= _MIN_ROW_BUCKET:
        return _MIN_ROW_BUCKET
    if n <= _POW2_ROW_CEILING:
        return round_up_pow2(n)
    c = _POW2_ROW_CEILING
    return ((n + c - 1) // c) * c


def bucket_width(w: int) -> int:
    """Padded node count for a stacked tree ensemble.  Trees grown across
    rounds drift in node count; rounding the pad width to a power of two keeps
    the stacked (T, M) shape — and therefore the compiled program — stable, so
    training-eval stops retracing every time a round yields a bushier tree."""
    return round_up_pow2(max(int(w), 2))


def pad_rows(X, bucket: int):
    """Pad a (R, F) batch with NaN rows up to ``bucket``.  No-op (no copy, no
    retrace) when the row count already matches the compiled shape."""
    R = X.shape[0]
    if R == bucket:
        return X
    return jnp.pad(X, ((0, bucket - R), (0, 0)), constant_values=jnp.nan)


def pad_margin(init, bucket: int):
    """Pad an optional (R, K) starting margin to the row bucket with zeros."""
    if init is None:
        return None
    R = init.shape[0]
    if R == bucket:
        return init
    return jnp.pad(init, ((0, bucket - R), (0, 0)))


def predict_cache_size() -> int:
    """Total compiled-program count across the predict entry points — the
    serving engine's recompile gauge (zero growth after warm-up is the SLO)."""
    return sum(
        f._cache_size()
        for f in (predict_margin_delta, predict_margin_delta_multi,
                  predict_leaf_ids, predict_margin_delta_binned)
    )


def run_stacked_margin(X_dev, stacked, groups, depth: int, n_groups: int,
                       init=None):
    """Dispatch a bucket-padded (B, F) batch through the jitted margin kernel
    matching the stacked-ensemble layout (multi-target value vectors,
    categorical masks, or plain scalar leaves).  The single place the stacked
    dict's field contract is interpreted — Booster prediction and the serving
    snapshot both route here so their outputs stay bitwise-identical."""
    if "value_vec" in stacked:
        return predict_margin_delta_multi(
            X_dev, stacked["feat"], stacked["thr"], stacked["dleft"],
            stacked["left"], stacked["right"], stacked["value_vec"],
            init, depth=depth)
    if stacked["catm"] is not None:
        return predict_margin_delta(
            X_dev, stacked["feat"], stacked["thr"], stacked["dleft"],
            stacked["left"], stacked["right"], stacked["value"],
            groups, stacked["is_cat"], stacked["catm"], init,
            n_groups=n_groups, depth=depth)
    return predict_margin_delta(
        X_dev, stacked["feat"], stacked["thr"], stacked["dleft"],
        stacked["left"], stacked["right"], stacked["value"],
        groups, init=init, n_groups=n_groups, depth=depth)


def _traverse_one_tree(X, feat, thr, dleft, left, right, depth: int,
                       is_cat=None, catm=None):
    """Leaf node id per row for one tree. X: (R,F) f32 with NaN missing.

    Categorical nodes route by set membership (in-set -> right, out-of-range
    -> left), matching common/categorical.h Decision."""
    R, F = X.shape
    nid = jnp.zeros(R, jnp.int32)

    def step(_, nid):
        fi = feat[nid]  # (R,) int32, -1 at leaves
        leaf = fi < 0
        x = jnp.take_along_axis(X, jnp.clip(fi, 0, F - 1)[:, None], axis=1)[:, 0]
        gol_num = x < thr[nid]
        if is_cat is None:
            gol = jnp.where(jnp.isnan(x), dleft[nid], gol_num)
        else:
            Bc = catm.shape[1]
            c = jnp.nan_to_num(x, nan=-1.0).astype(jnp.int32)
            in_range = (c >= 0) & (c < Bc)
            member = catm.reshape(-1)[nid * Bc + jnp.clip(c, 0, Bc - 1)] & in_range
            gol = jnp.where(is_cat[nid], ~member, gol_num)
            gol = jnp.where(jnp.isnan(x), dleft[nid], gol)
        nxt = jnp.where(gol, left[nid], right[nid])
        return jnp.where(leaf, nid, nxt)

    return lax.fori_loop(0, depth, step, nid)


def _native_predict_ok() -> bool:
    """CPU-backend gate for the native traversal kernels (same per-host
    agreement rules as the hist/split kernels — utils/native.py)."""
    import os

    if os.environ.get("XTB_NO_NATIVE_PREDICT", ""):
        return False
    if jax.default_backend() != "cpu":
        return False
    from ..utils import native

    return native.ffi_usable()


def _predict_native(X, feat, thr, dleft, left, right, value, groups,
                    is_cat, catm, init, n_groups: int, depth: int):
    """FFI custom call into xtb_predict_raw_impl — rows outer, trees inner,
    per-row adds in tree order (bitwise-identical to the XLA scan).  The
    kernel row-block-shards across the ParallelFor pool; output is bitwise
    identical for every nthread."""
    import numpy as np

    from ..utils import native

    native.ensure_pool()
    R = X.shape[0]
    T, M = feat.shape
    has_cat = is_cat is not None
    ic = (is_cat.astype(jnp.uint8) if has_cat
          else jnp.zeros((T, M), jnp.uint8))
    cm = (catm.astype(jnp.uint8) if has_cat
          else jnp.zeros((T, M, 1), jnp.uint8))
    init_arr = (jnp.zeros((R, n_groups), jnp.float32) if init is None
                else init.astype(jnp.float32))
    call = jax.ffi.ffi_call(
        "xtb_predict", jax.ShapeDtypeStruct((R, n_groups), jnp.float32))
    return call(X.astype(jnp.float32), feat.astype(jnp.int32),
                thr.astype(jnp.float32), dleft.astype(jnp.uint8),
                left.astype(jnp.int32), right.astype(jnp.int32),
                value.astype(jnp.float32), groups.astype(jnp.int32),
                ic, cm, init_arr,
                depth=np.int32(depth), has_cat=np.int32(has_cat))


@functools.partial(jax.jit, static_argnames=("n_groups", "depth"))
def predict_margin_delta(X, feat, thr, dleft, left, right, value, groups,
                         is_cat=None, catm=None, init=None, *,
                         n_groups: int, depth: int):
    """Sum leaf values of a stack of trees into (R, n_groups) margin deltas.

    feat..value : (T, M) stacked padded tree arrays; groups: (T,) int32
    (tree_info group ids, reference src/gbm/gbtree_model.h).
    is_cat (T, M) / catm (T, M, Bc): optional categorical routing tables.
    init: optional (R, n_groups) starting margin — accumulating INTO it
    reproduces the training loop's exact f32 addition order, so rebuilt
    prediction caches are bitwise-identical to incrementally-updated ones
    (continuation via xgb_model= yields the same model as one straight run).
    """
    if _native_predict_ok():
        return _predict_native(X, feat, thr, dleft, left, right, value,
                               groups, is_cat, catm, init, n_groups, depth)
    R = X.shape[0]

    def body(margin, t):
        if is_cat is None:
            f, th, dl, l, r, v, grp = t
            nid = _traverse_one_tree(X, f, th, dl, l, r, depth)
        else:
            f, th, dl, l, r, v, grp, ic, cm = t
            nid = _traverse_one_tree(X, f, th, dl, l, r, depth, ic, cm)
        delta = v[nid]
        col = lax.dynamic_slice_in_dim(margin, grp, 1, axis=1)
        margin = lax.dynamic_update_slice_in_dim(margin, col + delta[:, None], grp, axis=1)
        return margin, None

    xs = ((feat, thr, dleft, left, right, value, groups) if is_cat is None
          else (feat, thr, dleft, left, right, value, groups, is_cat, catm))
    with jax.named_scope("predict"):
        margin0 = (jnp.zeros((R, n_groups), jnp.float32) if init is None
                   else init.astype(jnp.float32))
        margin, _ = lax.scan(body, margin0, xs)
    return margin


@functools.partial(jax.jit, static_argnames=("depth",))
def predict_margin_delta_multi(X, feat, thr, dleft, left, right, value_vec,
                               init=None, *, depth: int):
    """Vector-leaf ensemble margins: every tree adds its leaf's K-vector to
    all outputs (reference: MultiTargetTree prediction,
    cpu_predictor.cc PredictBatchByBlockKernel vector-leaf path).

    value_vec: (T, M, K) padded per-node leaf vectors.  ``init``: optional
    starting margin (see predict_margin_delta)."""
    if _native_predict_ok():
        # K_leaf > 1 makes the kernel add each leaf vector to all K columns;
        # groups is unused on that path
        T = feat.shape[0]
        return _predict_native(X, feat, thr, dleft, left, right, value_vec,
                               jnp.zeros(T, jnp.int32), None, None, init,
                               value_vec.shape[2], depth)
    R = X.shape[0]
    K = value_vec.shape[2]

    def body(margin, t):
        f, th, dl, l, r, v = t
        nid = _traverse_one_tree(X, f, th, dl, l, r, depth)
        return margin + v[nid], None

    margin0 = (jnp.zeros((R, K), jnp.float32) if init is None
               else init.astype(jnp.float32))
    margin, _ = lax.scan(body, margin0,
                         (feat, thr, dleft, left, right, value_vec))
    return margin


@functools.partial(jax.jit, static_argnames=("depth",))
def predict_leaf_ids(X, feat, thr, dleft, left, right, *, depth: int):
    """(R, T) leaf indices (reference: Predictor::PredictLeaf)."""
    def body(_, t):
        f, th, dl, l, r = t
        return None, _traverse_one_tree(X, f, th, dl, l, r, depth)

    _, nids = lax.scan(body, None, (feat, thr, dleft, left, right))
    return nids.T


@functools.partial(jax.jit, static_argnames=("n_groups", "depth", "n_bin"))
def predict_margin_delta_binned(bins, feat, sbin, dleft, left, right, value,
                                groups, is_cat=None, catm=None, init=None, *,
                                n_groups: int, depth: int, n_bin: int):
    """Ensemble margins over a BINNED page (external-memory predict path).

    Routing uses stored split bins (RegTree.split_bins) so it reproduces the
    training-time partition exactly; sentinel n_bin = missing.  ``init``:
    optional starting margin (see predict_margin_delta — bitwise-faithful
    prediction-cache rebuilds).
    """
    if _native_predict_ok():
        import numpy as np

        from ..utils import native

        native.ensure_pool()
        R = bins.shape[0]
        T, M = feat.shape
        has_cat = is_cat is not None
        ic = (is_cat.astype(jnp.uint8) if has_cat
              else jnp.zeros((T, M), jnp.uint8))
        cm = (catm.astype(jnp.uint8) if has_cat
              else jnp.zeros((T, M, 1), jnp.uint8))
        init_arr = (jnp.zeros((R, n_groups), jnp.float32) if init is None
                    else init.astype(jnp.float32))
        b = bins
        if b.dtype not in (jnp.uint8, jnp.uint16, jnp.int16, jnp.int32):
            b = b.astype(jnp.int32)
        call = jax.ffi.ffi_call(
            "xtb_predict_binned",
            jax.ShapeDtypeStruct((R, n_groups), jnp.float32))
        return call(b, feat.astype(jnp.int32), sbin.astype(jnp.int32),
                    dleft.astype(jnp.uint8), left.astype(jnp.int32),
                    right.astype(jnp.int32), value.astype(jnp.float32),
                    groups.astype(jnp.int32), ic, cm, init_arr,
                    depth=np.int32(depth), has_cat=np.int32(has_cat),
                    n_bin=np.int32(n_bin))
    R = bins.shape[0]

    def traverse(f, sb, dl, l, r, ic, cm):
        nid = jnp.zeros(R, jnp.int32)

        def step(_, nid):
            fi = f[nid]
            leaf = fi < 0
            b = jnp.take_along_axis(
                bins, jnp.clip(fi, 0, bins.shape[1] - 1)[:, None].astype(jnp.int32),
                axis=1)[:, 0].astype(jnp.int32)
            gol_num = b <= sb[nid]
            if ic is not None:
                Bc = cm.shape[1]
                member = cm.reshape(-1)[nid * Bc + jnp.clip(b, 0, Bc - 1)] & (b < Bc)
                gol = jnp.where(ic[nid], ~member, gol_num)
            else:
                gol = gol_num
            gol = jnp.where(b >= n_bin, dl[nid], gol)  # sentinel = missing
            nxt = jnp.where(gol, l[nid], r[nid])
            return jnp.where(leaf, nid, nxt)

        return lax.fori_loop(0, depth, step, nid)

    def body(margin, t):
        if is_cat is None:
            f, sb, dl, l, r, v, grp = t
            nid = traverse(f, sb, dl, l, r, None, None)
        else:
            f, sb, dl, l, r, v, grp, ic, cm = t
            nid = traverse(f, sb, dl, l, r, ic, cm)
        delta = v[nid]
        col = lax.dynamic_slice_in_dim(margin, grp, 1, axis=1)
        margin = lax.dynamic_update_slice_in_dim(margin, col + delta[:, None], grp, axis=1)
        return margin, None

    xs = ((feat, sbin, dleft, left, right, value, groups) if is_cat is None
          else (feat, sbin, dleft, left, right, value, groups, is_cat, catm))
    with jax.named_scope("predict"):
        margin0 = (jnp.zeros((R, n_groups), jnp.float32) if init is None
                   else init.astype(jnp.float32))
        margin, _ = lax.scan(body, margin0, xs)
    return margin
