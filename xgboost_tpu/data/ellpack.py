"""EllpackPage: the device-resident binned feature matrix.

TPU-native analogue of the reference's EllpackPage / GHistIndexMatrix
(src/data/ellpack_page.cuh:26 EllpackAccessorImpl, src/data/gradient_index.h:43).
The reference stores bit-packed global bin indices with a fixed row stride; on
TPU we store a dense (R_pad, F) matrix of *feature-local* bin indices in the
smallest integer dtype that fits, padded so every feature has the same bin
width B — regular shapes are what XLA tiles well, and the histogram kernel
builds its one-hot from local indices directly.

Missing values use the sentinel bin ``B`` (one past the padded width): its
one-hot row is all-zero, so missing rows simply don't contribute to histograms,
matching the reference where missing entries are absent from Ellpack and the
split evaluator routes them via the learned default direction.

Row padding: rows are padded to a multiple of ``row_align`` with sentinel bins
and position -1 so chunked kernels see static shapes; padded rows carry zero
gradients and never match a node mask.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

from .quantile import HistogramCuts

MISSING_SENTINEL = "B"  # documented: sentinel == padded width B

# The binning program's search carries two int32 (R, F) arrays and a
# transposed copy of its float32 input: 15.8 B of temporaries a cell as the
# chip's compiler counts them, 14.5 GB at 946,997 x 968, more than a v5e holds
# beside the input (PERF.md section 6, PR 34).  A matrix of more cells than this
# is binned a block of rows at a time, a quarter of this many cells each, by
# the same program; one of fewer in one call (10.5M x 28 is 0.29G cells).
_BIN_CELLS = 1 << 29


def _bin_dtype(n_symbols: int):
    import jax.numpy as jnp

    if n_symbols <= 255:
        return jnp.uint8
    if n_symbols <= 32766:
        return jnp.int16
    return jnp.int32


@dataclasses.dataclass
class EllpackPage:
    """Device binned matrix + cut metadata.

    bins      : (R_pad, F) int — local bin index in [0, n_bins(f)), sentinel=B.
    cuts_pad  : (F, B) f32 — padded cut upper bounds (+inf pads).
    n_bins    : (F,) int32 — valid bin count per feature.
    n_rows    : logical row count (R_pad >= n_rows).
    """

    bins: "object"
    cuts_pad: "object"
    n_bins: "object"
    n_rows: int
    cuts: HistogramCuts

    @property
    def n_features(self) -> int:
        return int(self.bins.shape[1])

    @property
    def n_padded(self) -> int:
        return int(self.bins.shape[0])

    @property
    def bin_width(self) -> int:
        return int(self.cuts_pad.shape[1])

    @functools.cached_property
    def tiers(self):
        """The columns by the height their one-hot needs, from the ragged
        cuts (ops/histogram.py ``bin_tiers``); None: one tier of B bins."""
        from ..ops.histogram import bin_tiers

        return bin_tiers(self.cuts.n_bins_array(), self.bin_width)


def build_ellpack(
    X,
    cuts: HistogramCuts,
    row_align: int = 1024,
    device=None,
) -> EllpackPage:
    """Bin a dense (R, F) float matrix against ``cuts`` on device.

    bin = searchsorted(cuts_f, v, side='right') == count of cuts <= v, matching
    the reference's upper_bound search (src/common/hist_util.h SearchBin);
    values past the last cut are clamped into the top bin, NaN -> sentinel B.
    """
    import jax
    import jax.numpy as jnp

    R, F = X.shape
    assert F == cuts.n_features
    B = cuts.max_n_bins
    R_pad = ((R + row_align - 1) // row_align) * row_align
    cuts_pad = jnp.asarray(cuts.padded(B))  # (F, B), +inf padded
    n_bins = jnp.asarray(cuts.n_bins_array())  # (F,)
    dtype = _bin_dtype(B + 1)

    # native ingestion fast path (CPU backend): the threaded row-sharded
    # binning kernel streams X once, row-major, and writes the page
    # sequentially — bitwise-equal to the XLA searchsorted formulation
    # below (upper_bound + top-bin clamp + NaN sentinel), pinned by
    # tests/test_native_threads.py::test_ellpack_native_bin_parity
    if jax.default_backend() == "cpu":
        from ..utils import native as _native

        binned = _native.ellpack_bin_native(
            np.asarray(X, np.float32), cuts.cut_values, cuts.cut_ptrs, B,
            np.dtype(dtype))
        if binned is not None:
            bins = jnp.asarray(binned)
            if R_pad != R:
                pad = jnp.full((R_pad - R, F), B, dtype=dtype)
                bins = jnp.concatenate([bins, pad], axis=0)
            return EllpackPage(bins=bins, cuts_pad=cuts_pad, n_bins=n_bins,
                               n_rows=R, cuts=cuts)

    Xd = jnp.asarray(X, dtype=jnp.float32)

    @jax.jit
    def _bin(Xd):
        # vectorized per-feature searchsorted: count cuts <= v
        def one_feature(col, fcuts, nb):
            b = jnp.searchsorted(fcuts, col, side="right").astype(jnp.int32)
            b = jnp.minimum(b, nb - 1)  # clamp overflow into top bin
            b = jnp.where(jnp.isnan(col), B, b)
            return b

        with jax.named_scope("bin"):
            bins = jax.vmap(one_feature, in_axes=(1, 0, 0), out_axes=1)(
                Xd, cuts_pad, n_bins)
            return bins.astype(dtype)

    bins = _in_row_blocks(_bin, Xd)
    if R_pad != R:
        pad = jnp.full((R_pad - R, F), B, dtype=dtype)
        bins = jnp.concatenate([bins, pad], axis=0)
    return EllpackPage(bins=bins, cuts_pad=cuts_pad, n_bins=n_bins, n_rows=R, cuts=cuts)


def _in_row_blocks(bin_rows, Xd):
    """``bin_rows(Xd)``, a block of rows at a time where ``Xd`` holds more
    than ``_BIN_CELLS`` cells.  Every block has the same number of rows, so
    one program bins them all: the last one starts early enough to be whole,
    and what it holds of its neighbour is dropped."""
    import jax.numpy as jnp

    R, F = Xd.shape
    if R * F <= _BIN_CELLS:
        return bin_rows(Xd)
    n_blocks = -(-R * F // (_BIN_CELLS // 4))
    T = -(-R // n_blocks)
    starts = [min(i * T, R - T) for i in range(n_blocks)]
    blocks = [bin_rows(Xd[lo:lo + T]) for lo in starts]
    blocks[-1] = blocks[-1][n_blocks * T - R:]
    return jnp.concatenate(blocks, axis=0)


def count_missing(page: EllpackPage) -> tuple:
    """(cells, absent) of the page's logical rows: how many hold the
    sentinel.  Counted a column on the device and summed on the host, where
    the count may pass 2**31; waits for the page."""
    import jax
    import jax.numpy as jnp

    rows, sentinel = page.n_rows, page.bin_width
    by_column = jax.jit(lambda bins: jnp.sum(
        bins[:rows] == sentinel, axis=0, dtype=jnp.int32))(page.bins)
    return (rows * page.n_features,
            int(np.asarray(by_column).sum(dtype=np.int64)))


def build_ellpack_csr(indptr, indices, values, n_features: int, cuts: HistogramCuts,
                      row_align: int = 1024) -> EllpackPage:
    """Bin CSR input: implicit zeros are missing (sentinel), stored values binned.

    Host-side scatter into the dense local-bin layout; the result ships to
    device once.  (Reference: GHistIndexMatrix::PushBatch over SparsePage rows.)
    """
    import jax.numpy as jnp

    R = len(indptr) - 1
    B = cuts.max_n_bins
    dense = np.full((R, n_features), np.int32(B), dtype=np.int32)
    ptrs = cuts.cut_ptrs
    vals_all = cuts.cut_values
    row_of = np.repeat(np.arange(R), np.diff(indptr))
    v = values.astype(np.float32)
    ok = ~np.isnan(v)
    f = indices[ok]
    r = row_of[ok]
    vv = v[ok]
    # per-entry searchsorted within feature segment
    b = np.empty(len(vv), dtype=np.int32)
    for feat in np.unique(f):
        m = f == feat
        seg = vals_all[ptrs[feat] : ptrs[feat + 1]]
        bb = np.searchsorted(seg, vv[m], side="right")
        b[m] = np.minimum(bb, len(seg) - 1)
    dense[r, f] = b
    R_pad = ((R + row_align - 1) // row_align) * row_align
    if R_pad != R:
        dense = np.concatenate([dense, np.full((R_pad - R, n_features), B, np.int32)], axis=0)
    dtype = _bin_dtype(B + 1)
    return EllpackPage(
        bins=jnp.asarray(dense, dtype=dtype),
        cuts_pad=jnp.asarray(cuts.padded(B)),
        n_bins=jnp.asarray(cuts.n_bins_array()),
        n_rows=R,
        cuts=cuts,
    )
