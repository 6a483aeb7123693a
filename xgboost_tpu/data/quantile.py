"""Quantile sketch -> histogram bin boundaries.

TPU-native equivalent of the reference's quantile sketching + ``HistogramCuts``
(src/common/quantile.h:565 WQuantileSketch, src/common/hist_util.h:39-106
HistogramCuts, GPU fused sketch src/common/quantile.cu).  The reference runs a
GK merge-prune summary per feature; on TPU the data already lives on device as a
dense array, so we compute (weighted) quantiles directly with a device sort —
O(R log R) on the sorted axis, one pass, no summary machinery — and finalize the
ragged per-feature cut arrays on host.  Distributed merging (quantile.cc:397-442
AllreduceV of summaries) becomes an all-gather of fixed-size per-shard quantile
grids (see parallel/collective.py).

Cut semantics match the reference (hist_util.cc):
 - bin b of feature f covers values v with cuts[b-1] <= v < cuts[b]
   (bin index = count of cuts <= v, i.e. searchsorted side='right');
 - the last cut is strictly greater than the feature max so every finite value
   lands in a valid bin;
 - ``min_vals`` records a value strictly below the feature min.
"""
from __future__ import annotations

import dataclasses
import secrets
from typing import List, Optional, Sequence

import numpy as np


def _fresh_cuts_token() -> int:
    """Collision-proof across processes: cuts_token survives Booster pickling,
    so a process-local counter could falsely match an unpickled model's trees
    against an unrelated DMatrix's cuts (each process's first cuts would share
    token 1) and reuse stale split_bins."""
    return secrets.randbits(63)


@dataclasses.dataclass
class HistogramCuts:
    """Bin boundaries (reference: src/common/hist_util.h:39-106).

    ``cut_ptrs``  : (F+1,) int32  — CSR offsets into ``cut_values``.
    ``cut_values``: (total_bins,) f32 — ascending per-feature upper bounds.
    ``min_vals``  : (F,) f32 — strictly below each feature's min.
    """

    cut_ptrs: np.ndarray
    cut_values: np.ndarray
    min_vals: np.ndarray
    # process-unique identity: trees grown against these cuts record the token
    # so binned predict routes can verify their split_bins index THESE cuts
    # (a Booster continued on a different DMatrix must not reuse stale bins)
    token: int = dataclasses.field(default_factory=_fresh_cuts_token)

    @property
    def n_features(self) -> int:
        return len(self.cut_ptrs) - 1

    @property
    def total_bins(self) -> int:
        return int(self.cut_ptrs[-1])

    def n_bins(self, f: int) -> int:
        return int(self.cut_ptrs[f + 1] - self.cut_ptrs[f])

    @property
    def max_n_bins(self) -> int:
        return int(np.max(np.diff(self.cut_ptrs))) if self.n_features else 0

    def feature_cuts(self, f: int) -> np.ndarray:
        return self.cut_values[self.cut_ptrs[f] : self.cut_ptrs[f + 1]]

    def padded(self, width: Optional[int] = None) -> np.ndarray:
        """Dense (F, B) cut matrix padded with +inf — the jit-friendly layout.

        Padded slots never win a split because their histogram mass is zero and
        the evaluator masks bins >= n_bins(f).
        """
        B = width or self.max_n_bins
        out = np.full((self.n_features, B), np.inf, dtype=np.float32)
        for f in range(self.n_features):
            seg = self.feature_cuts(f)
            out[f, : len(seg)] = seg
        return out

    def n_bins_array(self) -> np.ndarray:
        return np.diff(self.cut_ptrs).astype(np.int32)


def _final_cut(vmax: float) -> float:
    # Reference hist_util.cc appends max + small delta so max lands in the last bin.
    return float(vmax + (abs(vmax) * 1e-2 if vmax != 0.0 else 1e-5) + 1e-5)


def cuts_from_quantile_grid(
    grid: np.ndarray, n_valid: np.ndarray, vmax: np.ndarray, vmin: np.ndarray
) -> HistogramCuts:
    """Finalize ragged cuts from a dense (F, Q) quantile grid.

    grid[f, q] is the q-th quantile candidate of feature f (rows with
    n_valid[f]==0 are all-NaN features).  Dedupes per feature and appends the
    open upper bound.
    """
    F, _ = grid.shape
    ptrs = [0]
    values: List[np.ndarray] = []
    mins = np.empty(F, dtype=np.float32)
    for f in range(F):
        if n_valid[f] == 0:
            seg = np.array([1e-5], dtype=np.float32)  # single catch-all bin
            mins[f] = -1e-5
        else:
            cand = np.unique(grid[f][np.isfinite(grid[f])])
            # drop candidates that equal the running max; the final cut covers them
            last = _final_cut(float(vmax[f]))
            cand = cand[cand < last]
            # candidates must exceed the feature min so bin 0 is non-empty-able
            seg = np.append(cand[cand > vmin[f]], np.float32(last)).astype(np.float32)
            mins[f] = vmin[f] - (abs(vmin[f]) * 1e-2 if vmin[f] != 0 else 1e-5)
        values.append(seg)
        ptrs.append(ptrs[-1] + len(seg))
    return HistogramCuts(
        cut_ptrs=np.asarray(ptrs, dtype=np.int32),
        cut_values=np.concatenate(values).astype(np.float32) if values else np.zeros(0, np.float32),
        min_vals=mins,
    )


def categorical_cuts(n_cats: int) -> np.ndarray:
    """Identity cuts for a categorical feature: code c lands in bin c
    (cuts [1..n_cats]; searchsorted side='right' of code c gives c)."""
    return np.arange(1, max(n_cats, 1) + 1, dtype=np.float32)


def _assemble_cuts(F: int, cat_n_cats, num_seg) -> HistogramCuts:
    """Stitch per-feature cut segments: identity cuts for categorical
    features (cat_n_cats: {feature -> n_cats}), ``num_seg(f) -> (seg, min)``
    for numeric ones.  Shared by every sketch flavour.  A categorical
    column's bins are its categories, however many: ``max_bin`` bounds the
    numeric columns alone (upstream's HistogramCuts, where a category is a
    cut), and the page's common width follows the widest column."""
    ptrs, values = [0], []
    mins = np.zeros(F, np.float32)
    for f in range(F):
        if f in cat_n_cats:
            seg = categorical_cuts(cat_n_cats[f])
            mins[f] = -1e-5
        else:
            seg, mins[f] = num_seg(f)
        values.append(seg)
        ptrs.append(ptrs[-1] + len(seg))
    return HistogramCuts(
        np.asarray(ptrs, np.int32),
        np.concatenate(values).astype(np.float32) if values else np.zeros(0, np.float32),
        mins)


def sketch_dense(
    X,
    max_bin: int,
    weights: Optional[np.ndarray] = None,
    use_device: bool = True,
    cat_mask: Optional[np.ndarray] = None,
) -> HistogramCuts:
    """Build HistogramCuts from a dense (R, F) float matrix with NaN = missing.

    Device path: one jnp.sort per feature column block + a gather at quantile
    positions; only the (F, max_bin) grid is pulled back to host (the analogue
    of the reference's device sketch returning pruned summaries,
    src/common/hist_util.cuh:213 DeviceSketch).
    Weighted data falls back to a host weighted-CDF quantile (reference:
    WQSketch handles weights natively).
    """
    Xn = np.asarray(X, dtype=np.float32) if not hasattr(X, "devices") else X
    R, F = Xn.shape
    n_cand = max(max_bin - 1, 1)

    if cat_mask is not None and np.any(cat_mask):
        # categorical columns get identity cuts; only numeric columns are
        # sketched (reference: CatContainer ordinal encoding, cat_container)
        Xh = np.asarray(Xn)
        num_idx = np.nonzero(~cat_mask)[0]
        base = (sketch_dense(Xh[:, num_idx], max_bin, weights=weights,
                             use_device=use_device)
                if len(num_idx) else None)
        cat_n_cats = {}
        for f in np.nonzero(cat_mask)[0]:
            col = Xh[:, f]
            col = col[~np.isnan(col)]
            cat_n_cats[int(f)] = int(col.max()) + 1 if len(col) else 1
        num_pos = {int(f): i for i, f in enumerate(num_idx)}
        return _assemble_cuts(
            F, cat_n_cats,
            lambda f: (base.feature_cuts(num_pos[f]), base.min_vals[num_pos[f]]))

    if weights is not None:
        return _sketch_weighted_host(np.asarray(Xn, dtype=np.float32), max_bin, np.asarray(weights))

    if use_device and R * F > 0:
        import jax
        import jax.numpy as jnp

        import os as _os

        if (jax.default_backend() == "cpu"
                and not _os.environ.get("XTB_FORCE_DEVICE_SKETCH")):
            # XLA's CPU sort is ~20x slower than numpy's (measured 27s vs
            # 1.7s for 1M x 28); the host grid is exact and fast there.
            # XTB_FORCE_DEVICE_SKETCH=1 keeps the accelerator code path
            # CI-covered on the CPU backend (tests/test_basic.py).
            return _sketch_weighted_host(np.asarray(Xn, np.float32),
                                         max_bin, None)

        Xd = jnp.asarray(Xn, dtype=jnp.float32)
        # accelerator sorts are bitonic (O(R log^2 R) HBM passes): above
        # ~2^19 rows a deterministic stride subsample makes the sketch
        # O(sample) with quantile error O(1/sqrt(sample)) — well inside the
        # binning tolerance (the reference's streaming sketch is likewise
        # eps-approximate, src/common/quantile.h); min/max stay exact via
        # full-data reductions below so the value range never clips
        SAMPLE = 1 << 19
        if R > SAMPLE:
            stride = (R + SAMPLE - 1) // SAMPLE
            Xs = Xd[::stride]
        else:
            Xs = Xd
        sortd = jnp.sort(Xs, axis=0)  # NaNs sort to the end
        nvalid = jnp.sum(~jnp.isnan(Xs), axis=0)  # (F,) of the sample
        # quantile candidate ranks: ceil(i/ncand * nvalid) - style positions
        qs = (jnp.arange(1, n_cand + 1, dtype=jnp.float32) / (n_cand + 1))
        # inverted-CDF ranks: ceil(q*n) - 1 (matches np.quantile inverted_cdf
        # and the native streaming summary, so every sketch path agrees)
        pos = jnp.clip(
            jnp.ceil(qs[None, :] * nvalid[:, None].astype(jnp.float32)).astype(jnp.int32) - 1,
            0, jnp.maximum(nvalid[:, None] - 1, 0))
        grid = jnp.take_along_axis(sortd.T, pos, axis=1)  # (F, n_cand)
        # exact extremes + true valid counts from the FULL data (cheap
        # reductions), so sampling cannot clip the value range or skew the
        # distributed merge's mass weighting
        nvalid_full = jnp.sum(~jnp.isnan(Xd), axis=0)
        vmax = jnp.nanmax(Xd, axis=0, initial=-jnp.inf)
        vmin = jnp.nanmin(Xd, axis=0, initial=jnp.inf)
        grid_h = np.asarray(grid)
        nvalid_h = np.asarray(nvalid_full)
        vmax_h = np.where(nvalid_h > 0, np.asarray(vmax), 0.0)
        vmin_h = np.where(nvalid_h > 0, np.asarray(vmin), 0.0)
        grid_h = np.where(np.isnan(grid_h), np.inf, grid_h)
        return cuts_from_quantile_grid(grid_h, nvalid_h, vmax_h, vmin_h)

    return _sketch_weighted_host(Xn, max_bin, None)


def _sketch_weighted_host(X: np.ndarray, max_bin: int, w: Optional[np.ndarray]) -> HistogramCuts:
    return cuts_from_quantile_grid(*_host_grid(X, max_bin, w)[:4])


def _host_grid(X: np.ndarray, max_bin: int, w: Optional[np.ndarray]):
    """Per-feature quantile candidate grid (F, max_bin-1) + stats — the
    fixed-size 'summary' exchanged by the distributed sketch merge.
    Returns (grid, nvalid, vmax, vmin, mass); mass is the per-feature total
    sample weight (== nvalid when unweighted), the quantity that weights this
    shard's candidates in the merge."""
    R, F = X.shape
    n_cand = max(max_bin - 1, 1)
    grid = np.full((F, n_cand), np.inf, dtype=np.float32)
    nvalid = np.zeros(F, dtype=np.int64)
    vmax = np.zeros(F, dtype=np.float32)
    vmin = np.zeros(F, dtype=np.float32)
    qs = np.arange(1, n_cand + 1, dtype=np.float64) / (n_cand + 1)
    if w is None:
        if R == 0:
            # empty shard: all-inf grid + zero counts, what the
            # distributed merge expects from a contribution-free rank
            return grid, nvalid, vmax, vmin, nvalid.astype(np.float64)
        # one whole-matrix sort (NaNs sort last) + rank gather — the same
        # inverted-CDF positions as the device path, ~20x faster than
        # per-column np.quantile at wide F
        sortd = np.sort(X, axis=0)
        nvalid[:] = np.sum(~np.isnan(X), axis=0)
        pos = np.clip(
            np.ceil(qs[None, :] * nvalid[:, None]).astype(np.int64) - 1,
            0, np.maximum(nvalid[:, None] - 1, 0))
        got = np.take_along_axis(sortd.T, pos, axis=1).astype(np.float32)
        has = nvalid > 0
        grid[has] = got[has]
        vmax[has] = np.take_along_axis(
            sortd.T, np.maximum(nvalid[:, None] - 1, 0), axis=1)[has, 0]
        vmin[has] = sortd[0][has]
        return grid, nvalid, vmax, vmin, nvalid.astype(np.float64)
    for f in range(F):
        col = X[:, f]
        mask = ~np.isnan(col)
        vals = col[mask]
        nvalid[f] = len(vals)
        if len(vals) == 0:
            continue
        vmax[f] = vals.max()
        vmin[f] = vals.min()
        if w is not None:
            wf = w[mask].astype(np.float64)
            order = np.argsort(vals, kind="stable")
            sv, sw = vals[order], wf[order]
            cdf = np.cumsum(sw)
            tot = cdf[-1]
            if tot <= 0:
                grid[f] = np.quantile(vals, qs, method="inverted_cdf").astype(np.float32)
            else:
                idx = np.searchsorted(cdf, qs * tot, side="left")
                grid[f] = sv[np.clip(idx, 0, len(sv) - 1)].astype(np.float32)
    # only the weighted path reaches here (w is None returned early)
    wq = np.asarray(w, np.float64)
    mass = np.array([wq[~np.isnan(X[:, f])].sum() for f in range(F)])
    return grid, nvalid, vmax, vmin, mass


def merge_quantile_grids(grids: np.ndarray, nvalids: np.ndarray,
                         vmaxs: np.ndarray, vmins: np.ndarray,
                         max_bin: int,
                         masses: Optional[np.ndarray] = None) -> HistogramCuts:
    """Merge per-worker quantile grids into shared cuts.

    The TPU-shaped analogue of the reference's summary allreduce
    (src/common/quantile.cc:397-442 SketchContainer::AllReduce): instead of
    merging GK summaries with rank bounds, every worker contributes a
    fixed-size quantile grid whose k-th worker candidates each carry an equal
    share of that worker's total sample-weight mass (masses[k,f], == nvalid
    when unweighted); the merged cuts are inverted-CDF quantiles of the
    weighted union.  Deterministic given the gathered inputs, so every worker
    computes bitwise-identical cuts.

    grids: (W, F, Q), nvalids/masses: (W, F), vmaxs/vmins: (W, F).
    """
    W, F, Q = grids.shape
    if masses is None:
        masses = nvalids.astype(np.float64)
    n_cand = max(max_bin - 1, 1)
    qs = np.arange(1, n_cand + 1, dtype=np.float64) / (n_cand + 1)
    grid = np.full((F, n_cand), np.inf, dtype=np.float32)
    nvalid = nvalids.sum(axis=0).astype(np.int64)
    vmax = np.zeros(F, dtype=np.float32)
    vmin = np.zeros(F, dtype=np.float32)
    for f in range(F):
        has = nvalids[:, f] > 0
        if not has.any():
            continue
        vmax[f] = vmaxs[has, f].max()
        vmin[f] = vmins[has, f].min()
        cand_list, w_list = [], []
        for k in np.nonzero(has)[0]:
            c = grids[k, f]
            c = c[np.isfinite(c)]
            if len(c) == 0:
                continue
            cand_list.append(c.astype(np.float64))
            w_list.append(np.full(len(c), masses[k, f] / len(c), np.float64))
        cand = np.concatenate(cand_list)
        wts = np.concatenate(w_list)
        order = np.argsort(cand, kind="stable")
        sv, sw = cand[order], wts[order]
        cdf = np.cumsum(sw)
        idx = np.searchsorted(cdf, qs * cdf[-1], side="left")
        grid[f] = sv[np.clip(idx, 0, len(sv) - 1)].astype(np.float32)
    return cuts_from_quantile_grid(grid, nvalid, vmax, vmin)


def _pack_contrib(grid: np.ndarray, nvalid: np.ndarray, vmax: np.ndarray,
                  vmin: np.ndarray, mass: np.ndarray) -> np.ndarray:
    """One page's sketch contribution as a single (F, Q+4) f64 block, so the
    whole per-rank summary crosses the collective in ONE ragged gather.
    f32 grid values and int64 counts round-trip exactly through f64."""
    F, Q = grid.shape
    out = np.empty((F, Q + 4), np.float64)
    out[:, :Q] = grid
    out[:, Q] = nvalid
    out[:, Q + 1] = vmax
    out[:, Q + 2] = vmin
    out[:, Q + 3] = mass
    return out


class StreamingSketch:
    """Page-at-a-time (distributed) quantile sketch.

    The out-of-core analogue of :func:`sketch_distributed`: instead of one
    grid from a materialized shard, every pushed page contributes one
    fixed-size summary — exactly what :func:`_host_grid` produces for that
    page — and ``finalize()`` merges ALL page contributions (across every
    rank when ``distributed=True``) through :func:`merge_quantile_grids`,
    so cuts never require the full matrix resident.

    Pinned contract (tests/test_extmem.py sketch-parity fuzz): **the page
    is the atomic sketch unit, and the merge is a pure function of the
    multiset of page contributions.**  Candidates are value-sorted inside
    :func:`merge_quantile_grids` and a tied value is selected by value, not
    position, so the merged cuts are bitwise-identical however the pages
    are grouped onto ranks (world 1/2/4/...) and in whatever order they
    are pushed — and equal to the one-shot :func:`sketch_distributed`
    where each page is one rank's whole shard.  Per-rank memory is
    O(pages x F x max_bin); the summary allreduce is one ragged gather of
    the packed page blocks plus (with categoricals) one MAX-allreduce.
    """

    def __init__(self, n_features: int, max_bin: int,
                 cat_mask: Optional[np.ndarray] = None) -> None:
        self.n_features = int(n_features)
        self.max_bin = int(max_bin)
        cm = None
        if cat_mask is not None and np.any(cat_mask):
            cm = np.asarray(cat_mask, bool)
            if len(cm) != self.n_features:
                raise ValueError("cat_mask length != n_features")
        self.cat_mask = cm
        self._contribs: List[np.ndarray] = []
        self._cat_max = np.full(self.n_features, -1.0, np.float32)

    @property
    def n_cand(self) -> int:
        return max(self.max_bin - 1, 1)

    @property
    def n_pages(self) -> int:
        return len(self._contribs)

    def push(self, X, weights: Optional[np.ndarray] = None) -> None:
        """Fold one dense (R, F) page (NaN = missing) into the sketch."""
        Xh = np.asarray(X, dtype=np.float32)
        if Xh.shape[1] != self.n_features:
            raise ValueError(
                f"page has {Xh.shape[1]} features, sketch expects "
                f"{self.n_features}")
        w = None if weights is None else np.asarray(weights)
        if self.cat_mask is None:
            self._contribs.append(_pack_contrib(*_host_grid(
                Xh, self.max_bin, w)))
            return
        # categorical columns never enter the numeric sort: their cuts come
        # from the global category max (a nanmax, not a quantile grid), and
        # their rows in the packed contribution stay the empty-feature
        # sentinel (inf grid / zero stats) the merge ignores
        cat = self.cat_mask
        for f in np.nonzero(cat)[0]:
            col = Xh[:, f]
            col = col[~np.isnan(col)]
            if len(col):
                self._cat_max[f] = max(self._cat_max[f], col.max())
        F, Q = self.n_features, self.n_cand
        grid = np.full((F, Q), np.inf, np.float32)
        nvalid = np.zeros(F, np.int64)
        vmax = np.zeros(F, np.float32)
        vmin = np.zeros(F, np.float32)
        mass = np.zeros(F, np.float64)
        num_idx = np.nonzero(~cat)[0]
        if len(num_idx):
            g, nv, vx, vn, ms = _host_grid(Xh[:, num_idx], self.max_bin, w)
            grid[num_idx] = g
            nvalid[num_idx] = nv
            vmax[num_idx] = vx
            vmin[num_idx] = vn
            mass[num_idx] = ms
        self._contribs.append(_pack_contrib(grid, nvalid, vmax, vmin, mass))

    def push_csr(self, indptr, indices, values,
                 weights: Optional[np.ndarray] = None) -> None:
        """Fold one CSR page (implicit zeros = missing, matching
        :func:`sketch_csr`) without densifying it."""
        grid, nvalid, vmax, vmin, mass, cat_local = _csr_grid(
            np.asarray(indptr), np.asarray(indices), np.asarray(values),
            self.n_features, self.max_bin,
            None if weights is None else np.asarray(weights), self.cat_mask)
        np.maximum(self._cat_max, cat_local, out=self._cat_max)
        self._contribs.append(_pack_contrib(grid, nvalid, vmax, vmin, mass))

    def finalize(self, distributed: bool = False) -> HistogramCuts:
        """Merge every page contribution into shared cuts.

        ``distributed=True`` gathers all ranks' packed page blocks in one
        ragged allgather (every rank computes bitwise-identical cuts); a
        rank may hold any number of pages, including zero, as long as the
        job holds at least one page overall — but every rank must CALL
        finalize (``ExtMemQuantileDMatrix`` additionally requires one
        batch per rank, since it learns the feature count from it)."""
        from .. import collective

        F, Q = self.n_features, self.n_cand
        local = (np.stack(self._contribs) if self._contribs
                 else np.zeros((0, F, Q + 4), np.float64))
        cat_max = self._cat_max
        if distributed:
            flat = local.reshape(local.shape[0], F * (Q + 4))
            local = collective.allgather_ragged(flat).reshape(-1, F, Q + 4)
            if self.cat_mask is not None:
                cat_max = collective.allreduce(cat_max, collective.Op.MAX)
        if local.shape[0] == 0:
            raise ValueError("StreamingSketch.finalize: no pages pushed")
        base = merge_quantile_grids(
            local[:, :, :Q].astype(np.float32),
            local[:, :, Q].astype(np.int64),
            local[:, :, Q + 1].astype(np.float32),
            local[:, :, Q + 2].astype(np.float32),
            self.max_bin, masses=local[:, :, Q + 3])
        if self.cat_mask is None:
            return base
        cat_n_cats = {int(f): (int(cat_max[f]) + 1 if cat_max[f] >= 0 else 1)
                      for f in np.nonzero(self.cat_mask)[0]}
        return _assemble_cuts(
            F, cat_n_cats,
            lambda f: (base.feature_cuts(f), base.min_vals[f]))


def sketch_distributed(X, max_bin: int, weights: Optional[np.ndarray] = None,
                       cat_mask: Optional[np.ndarray] = None) -> HistogramCuts:
    """Shared cuts across processes, each holding a row shard of X.

    One :class:`StreamingSketch` page per rank: local fixed-size grid ->
    one ragged gather -> deterministic merge; categorical features take
    identity cuts sized by the global category max."""
    Xh = np.asarray(X, dtype=np.float32)
    sk = StreamingSketch(Xh.shape[1], max_bin, cat_mask=cat_mask)
    sk.push(Xh, weights=weights)
    return sk.finalize(distributed=True)


def _csr_grid(indptr, indices, values, n_features: int, max_bin: int,
              weights: Optional[np.ndarray],
              cat_mask: Optional[np.ndarray]):
    """Per-feature quantile grid + stats of one CSR page — the CSR twin of
    :func:`_host_grid`, shared by :func:`sketch_csr` and
    :meth:`StreamingSketch.push_csr`.  Returns (grid, nvalid, vmax, vmin,
    mass, cat_local_max); categorical columns are excluded from the
    numeric grid (nvalid stays 0) and report their max code instead."""
    R = len(indptr) - 1
    n_cand = max(max_bin - 1, 1)
    grid = np.full((n_features, n_cand), np.inf, dtype=np.float32)
    nvalid = np.zeros(n_features, dtype=np.int64)
    vmax = np.zeros(n_features, dtype=np.float32)
    vmin = np.zeros(n_features, dtype=np.float32)
    mass = np.zeros(n_features, dtype=np.float64)
    cat_local_max = np.full(n_features, -1.0, np.float32)
    qs = np.arange(1, n_cand + 1, dtype=np.float64) / (n_cand + 1)
    # bucket values per column
    order = np.argsort(indices, kind="stable")
    col_sorted = indices[order]
    val_sorted = values[order]
    starts = np.searchsorted(col_sorted, np.arange(n_features + 1))
    if weights is not None:
        row_of = np.repeat(np.arange(R), np.diff(indptr))[order]
    is_cat = np.zeros(n_features, bool) if cat_mask is None else np.asarray(cat_mask)
    for f in range(n_features):
        seg = val_sorted[starts[f] : starts[f + 1]].astype(np.float32)
        keep = ~np.isnan(seg)
        vals = seg[keep]
        if is_cat[f]:
            # NOTE: CSR categorical needs explicit storage — implicit zeros
            # are missing, so category 0 must be stored explicitly.
            # nvalid stays 0: cat features are excluded from the numeric
            # grid merge (their cuts come from the category max below)
            if len(vals):
                cat_local_max[f] = vals.max()
            continue
        nvalid[f] = len(vals)
        if len(vals) == 0:
            continue
        vmax[f], vmin[f] = vals.max(), vals.min()
        if weights is None:
            mass[f] = len(vals)
            grid[f] = np.quantile(vals, qs, method="inverted_cdf").astype(np.float32)
        else:
            wf = weights[row_of[starts[f] : starts[f + 1]]][keep].astype(np.float64)
            o = np.argsort(vals, kind="stable")
            sv, sw = vals[o], wf[o]
            cdf = np.cumsum(sw)
            mass[f] = cdf[-1]
            idx = np.searchsorted(cdf, qs * cdf[-1], side="left")
            grid[f] = sv[np.clip(idx, 0, len(sv) - 1)].astype(np.float32)
    return grid, nvalid, vmax, vmin, mass, cat_local_max


def sketch_csr(indptr, indices, values, n_features: int, max_bin: int,
               weights: Optional[np.ndarray] = None,
               cat_mask: Optional[np.ndarray] = None,
               distributed: bool = False) -> HistogramCuts:
    """Sketch a CSR matrix column-by-column on host (sparse ingest path).

    Implicit zeros in sparse input are treated as missing, matching the
    reference's sparse DMatrix semantics (only stored entries are sketched,
    src/common/hist_util.cc SketchOnDMatrix walks nonzeros).
    ``distributed=True``: this process holds a row shard — one
    :class:`StreamingSketch` page per rank, merged across processes
    without ever densifying the shard.
    """
    if distributed:
        sk = StreamingSketch(n_features, max_bin, cat_mask=cat_mask)
        sk.push_csr(indptr, indices, values, weights=weights)
        return sk.finalize(distributed=True)
    grid, nvalid, vmax, vmin, _mass, cat_local_max = _csr_grid(
        indptr, indices, values, n_features, max_bin, weights, cat_mask)
    base = cuts_from_quantile_grid(grid, nvalid, vmax, vmin)
    is_cat = np.zeros(n_features, bool) if cat_mask is None else np.asarray(cat_mask)
    if not is_cat.any():
        return base
    cat_n_cats = {int(f): (int(cat_local_max[f]) + 1 if cat_local_max[f] >= 0 else 1)
                  for f in np.nonzero(is_cat)[0]}
    return _assemble_cuts(
        n_features, cat_n_cats,
        lambda f: (base.feature_cuts(f), base.min_vals[f]))
