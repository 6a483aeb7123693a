"""Pallas TPU histogram kernel — the fused form of ops/histogram.py.

The CUDA reference builds histograms with shared-memory atomics
(src/tree/gpu_hist/histogram.cu:37-120).  TPU has no atomics; the masked
one-hot matmul formulation (ops/histogram.py) is MXU-shaped, and XLA keeps the
one-hot operand out of HBM in the feature-major form written there, but pays
2.05 ps an element of the one-hot whatever is multiplied into it: three
bfloat16 passes for ``HIGHEST`` over an operand it builds through float32.
This kernel builds the one-hot in VMEM by hand, in the matmul's own narrow
type, so a level is one pass for each 128-row tile of its gradient operand
(PERF.md §5, §6 PR 37, PR 40).  HBM sees only: the transposed page read once
(R*F*itemsize bytes), the gradient operand read once per feature group, the
sums written once.

**One pass, the same float32 sums** (``onepass_histogram``: what a round runs
where ``ops/histogram.py:hist_form`` says so).  A float32 ``g`` is split into
three bfloat16 terms, ``hi = bf16(g)``, ``mid = bf16(g - hi)``, ``lo = g - hi
- mid`` (``split3``): 8 + 8 + 8 mantissa bits under float32's own exponent,
so ``hi + mid + lo == g`` bit for bit.  The terms ride the operand's rows
(``3*C*N`` of them: 6 at the root, 96 at sixteen built nodes), the one-hot is
0/1 in bfloat16, every product is exact and the accumulator is float32: the
arithmetic ``HIGHEST`` does with an exact 0/1 operand, in one pass for its
three.  After the grid the three blocks of sums are added in float32 in a
fixed order, ``(hi + mid) + lo``.

Layout — rows ride the 128-lane axis everywhere, so every block is
lane-dense and no value is ever sliced off the lane axis:
  inputs: bins_t (F, R) int, the page transposed once by whoever keeps it;
          vals_t (Cv, R) bf16|int8; pos (1, R) int32; node0 (1,) int32 in
          SMEM (scalar prefetch: a traced ``node0`` is an operand)
  grid = (F/FG feature groups, R/T row tiles)   [both arbitrary/sequential]
  per step: bins tile (FG, T) + operand tile (Cv, T) + pos tile (1, T) in VMEM
  out block (Cv*N, FG*B) stays VMEM-resident across the row-tile loop of one
  feature group (index_map ignores the row index) and accumulates
      hist[:, f*B:(f+1)*B] += gm @ onehot(bins_t[f]).T   # (Cv*N, T) x (B, T)^T
  where gm[c*N + n] = vals_t[c] masked to the rows sitting in node n.
The one-hot is the operand the MXU holds and the ``Cv*N`` operand rows stream
past it: on a v5e at 10.5M x 28 x 256 that reads 0.051 s at the root (6 rows),
0.076 s at sixteen built nodes (96), 0.149 and 0.297 s at 192 and 384, where
the one-hot streamed past the operand reads 0.100 s a 128-row tile of it, and
XLA's three passes 0.163, 0.186, 0.195 and 0.360 s (PERF.md §6, PR 37).
A column costs what its bins are tall: the columns of a tier under 128 bins
share a dot, eight of 32 bins or four of 64 side by side on its 256 lanes
(``_DOT_LANES``), so a narrow tier is never padded to 128 lanes a column.  The
last row tile and the last feature group may hang over the page: lanes past
``R`` are masked by the row's index, columns past ``F`` are sliced off.

One kernel body serves the one-pass form (Cv = 3 terms x 2 channels g,h;
bfloat16 x bfloat16 with float32 accumulate, at default precision: one MXU
pass, exact here) and the quantised form (Cv = 6 int8 limbs; int32
accumulate, exact and order-free — the reference's GradientQuantiser
contract, quantiser.cuh:52), which nothing in the library calls yet (ROADMAP
D1: it is ``deterministic_histogram``'s kernel once a cell asks for it).

Determinism: sequential grid, fixed accumulation order, no atomics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped-VMEM limit is 16 MiB; a v5e core has 128 MiB.  The
# kernel asks for _VMEM_LIMIT explicitly and choose_tiles plans the working
# set under _VMEM_BUDGET, the rest being the compiler's own temporaries (the
# role of the reference's CacheManager L1/L2 detection for CPU hist
# blocking, src/common/cache_manager.h).
_VMEM_LIMIT = 64 * 2**20
_VMEM_BUDGET = 40 * 2**20

_LANES = 128
# widest sublane packing among the bin dtypes (int8: 32 rows per tile); a
# feature group of this height is a legal block for uint8, int16 and int32
_FEAT_GROUP = 32
# lanes of the sums one dot fills: a column of 256 bins alone, eight columns
# of a 32-bin tier side by side
_DOT_LANES = 256
# the row tiles choose_tiles tries, largest first (v5e, 10.5M x 28 x 256, 16
# built nodes: 0.0757 s at 2,048, 0.0765 at 1,024, 0.0783 at 512)
_ROW_TILES = (2048, 1024, 512, 256)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bin_lanes(n_bin: int) -> int:
    """Lanes a column's bins take in the sums: 32, 64 or a multiple of 128,
    so that whole columns tile a dot's lanes; a bin in the pad (the
    sentinel, where ``n_bin`` is under it) is sliced off the sums."""
    return next((w for w in (32, 64) if n_bin <= w), _round_up(n_bin, _LANES))


def choose_tiles(n_features: int, n_bin: int, n_nodes: int,
                 bin_itemsize: int = 1,
                 vmem_budget: int = _VMEM_BUDGET, out_ch: int = 6) -> tuple:
    """Pick (row_tile, feat_group) that fits the VMEM budget.

    The feature group is not free: Mosaic takes a (FG, T) block of the
    (F, R) bins only when FG is the whole feature axis or a multiple of the
    dtype's sublane tile, and the columns that share a dot (``_DOT_LANES``)
    have to divide it: FG = F for narrow data where they do, and 32
    otherwise (hanging over the page where F is less).  The row tile is the
    largest of _ROW_TILES whose working set fits:
      - out block, double-buffered: 2 * roundup(out_ch*N, 8) * FG * B * 4
        (out_ch = 6 either way: three terms of (g,h), or (g,h) x 3 limbs)
      - double-buffered inputs: 2 * T * (FG*itemsize + 16*2 + 8*4)
      - scratch: widened bins FG*T*4, the one-hot of a dot and its int32
        compare 6 * _DOT_LANES * T, the masked operand's iota, channel, node,
        value, compare and result: 6 * roundup(out_ch*N, 8) * T * 4
    Always returns something; the compiler refuses what does not fit
    (``tiles_fit`` says whether anything does).
    """
    lanes = _bin_lanes(n_bin)
    fg = _feat_group(n_features, lanes)
    m = _round_up(out_ch * n_nodes, 8)
    for t in _ROW_TILES:
        if _working_set(t, fg, lanes, m, bin_itemsize) <= vmem_budget:
            return t, fg
    return _ROW_TILES[-1], fg


def _feat_group(n_features: int, lanes: int) -> int:
    fg = max(n_features, 1)
    if fg > _FEAT_GROUP or fg % max(1, _DOT_LANES // lanes):
        fg = _FEAT_GROUP
    return fg


def _working_set(t: int, fg: int, lanes: int, m: int,
                 bin_itemsize: int) -> int:
    """VMEM bytes of the kernel at row tile ``t`` (choose_tiles' terms)."""
    out_b = 2 * m * fg * lanes * 4
    in_b = 2 * t * (fg * bin_itemsize + 64)
    scratch = fg * t * 4 + 6 * max(_DOT_LANES, lanes) * t + 6 * m * t * 4
    return out_b + in_b + scratch


def tiles_fit(n_features: int, n_bin: int, n_nodes: int,
              out_ch: int = 6) -> bool:
    """Whether the smallest row tile's working set fits the VMEM budget, for
    bins of any integer type.  The double-buffered block of sums does not
    shrink with the row tile: at 384 operand rows a feature group of 32
    columns of 1,024 bins is 100 MB of it, and such a level keeps the XLA
    form (``ops/histogram.py:hist_form``)."""
    lanes = _bin_lanes(n_bin)
    return _working_set(_ROW_TILES[-1], _feat_group(n_features, lanes), lanes,
                        _round_up(out_ch * n_nodes, 8), 4) <= _VMEM_BUDGET


def _resolve_interpret(interpret):
    """``None`` = compile for the chip on TPU, interpret on CPU (so the
    kernels' tests run, slowly, without a chip).  Never
    interpret mode on a chip, never a guess on any other platform."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "the fused histogram kernel compiles for TPU and interprets on CPU; "
        f"JAX's default backend here is {backend!r}")


def split3(g):
    """``g`` float32 -> ``(hi, mid, lo)`` bfloat16 with ``(hi + mid) + lo ==
    g`` bit for bit in float32: ``hi = bf16(g)``, ``mid = bf16(g - hi)``,
    ``lo = g - hi - mid``, each rounding to nearest leaving at most 16, then
    8 bits, which bfloat16 holds (it has float32's exponent).  Exact for
    every finite ``|g|`` from 2**-102 (2.0e-31), under which the third term
    falls below bfloat16's normal range and is flushed (a residue under
    2**-126 = 1.2e-38 an element is dropped), up to bfloat16's largest,
    3.39e38 (above it ``bf16(g)`` is infinite, as anywhere).
    ``reduce_precision`` and not a cast there and back: XLA may fold
    ``f32(bf16(g))`` to ``g`` (excess precision), and the terms would be
    ``g, 0, 0`` rounded once."""
    g = g.astype(jnp.float32)
    hi = jax.lax.reduce_precision(g, exponent_bits=8, mantissa_bits=7)
    rest = g - hi
    mid = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


def _masked_operand(pos_row, vals, *, node0, n_nodes: int, stride: int):
    """(C*N, T) matmul operand, channel-major: row c*N + n holds channel c of
    ``vals`` (C, T) for the rows whose ``pos`` is node n, zero elsewhere.
    Built from a 2-D iota and selects only — no reshape, no concatenate, no
    integer division (none of which Mosaic takes at arbitrary C, N)."""
    C, T = vals.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (C * n_nodes, T), 0)
    ch = jnp.zeros_like(r)
    for c in range(1, C):
        ch = ch + (r >= c * n_nodes).astype(jnp.int32)
    node = node0 + stride * (r - ch * n_nodes)
    val = jnp.broadcast_to(vals[0:1, :], r.shape)
    for c in range(1, C):
        val = jnp.where(ch == c, vals[c:c + 1, :], val)
    return jnp.where(pos_row == node, val, jnp.zeros_like(val))


def _hist_kernel(node0_ref, bins_ref, vals_ref, pos_ref, out_ref, *,
                 n_rows: int, n_nodes: int, stride: int, lanes: int,
                 group: int):
    i = pl.program_id(1)

    @pl.when(i == 0)  # first row tile of this feature group
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    op_dtype = vals_ref.dtype  # bfloat16 | int8
    wide = jnp.int32 if jnp.issubdtype(op_dtype, jnp.integer) else jnp.float32
    FG, T = bins_ref.shape
    # the last tile's lanes past the page hold whatever the copy left there
    here = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < n_rows
    # 0/1 mask times a term or a limb is itself: exact in the narrow type
    gm = _masked_operand(jnp.where(here, pos_ref[...], -1),
                         vals_ref[...].astype(wide), node0=node0_ref[0],
                         n_nodes=n_nodes, stride=stride).astype(op_dtype)
    bins = bins_ref[...].astype(jnp.int32)  # (FG, T)
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (lanes, T), 0)
    for f in range(0, FG, group):  # static unroll
        # (group * lanes, T); the missing sentinel compares false or falls
        # in a column's pad, in a tier every bin at or above its height
        onehot = jnp.concatenate(
            [(bins[k:k + 1, :] == bin_ids).astype(wide).astype(op_dtype)
             for k in range(f, f + group)], axis=0)
        out_ref[:, f * lanes:(f + group) * lanes] += jax.lax.dot_general(
            gm, onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),  # contract rows
            preferred_element_type=out_ref.dtype)


def _fused_hist(bins_t, vals_t, pos, node0, *, n_nodes: int, n_bin: int,
                stride: int, interpret, row_tile: int, feat_group: int,
                acc_dtype):
    """Shared wrapper: the sums (Cv, N, F, B) from bins_t (F, R), vals_t
    (Cv, R), pos (R,), node0 an int or a traced scalar.  Nothing is padded:
    the grid hangs over the page and the kernel masks what lies past it."""
    interpret = _resolve_interpret(interpret)
    F, R = bins_t.shape
    Cv = vals_t.shape[0]
    M = Cv * n_nodes
    lanes = _bin_lanes(n_bin)
    T, FG = row_tile, feat_group
    if not (T and FG):
        at, afg = choose_tiles(F, n_bin, n_nodes, bins_t.dtype.itemsize,
                               out_ch=Cv)
        T, FG = T or at, FG or afg
    # the columns that share a dot: all that fit its lanes where they
    # divide the group (choose_tiles sees to it), else as many as do
    group = math.gcd(FG, max(1, _DOT_LANES // lanes))
    n_fg = -(-F // FG)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_rows=R, n_nodes=n_nodes,
                          stride=stride, lanes=lanes, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_fg, -(-R // T)),
            in_specs=[
                pl.BlockSpec((FG, T), lambda fg, i, n0: (fg, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((Cv, T), lambda fg, i, n0: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, T), lambda fg, i, n0: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((M, FG * lanes), lambda fg, i, n0: (0, fg),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((M, n_fg * FG * lanes), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * n_fg * FG * lanes * M,
            bytes_accessed=R * n_fg * FG * bins_t.dtype.itemsize
            + R * (Cv * vals_t.dtype.itemsize + 4) * n_fg
            + n_fg * FG * lanes * M * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(jnp.asarray(node0, jnp.int32).reshape(1), bins_t, vals_t,
      pos.astype(jnp.int32)[None, :])
    # (Cv * N, F_pad * lanes) -> (Cv, N, F, B)
    return out[:, :F * lanes].reshape(Cv, n_nodes, F, lanes)[..., :n_bin]


def onepass_histogram(bins_t, gpair, pos, node0, *, n_nodes: int, n_bin: int,
                      stride: int = 1, interpret=None, row_tile: int = 0,
                      feat_group: int = 0):
    """float32 hist (n_nodes, F, B, C) of nodes ``node0 + stride*[0,
    n_nodes)`` in one bfloat16 MXU pass (module docstring): what
    ``level_histogram`` calls, a tier at a time, inside the level programs.

    bins_t (F, R) int — the page transposed (``ops/histogram.py
    transposed_page``), sentinel >= n_bin for missing; gpair (R, C) f32; pos
    (R,) int32; ``node0`` an int or a traced scalar.  Every 128 rows of the
    ``3*C*n_nodes`` operand is one more pass over the one-hot: ``hist_form``
    stops at 384, a 128-slot level.  ``row_tile``/``feat_group`` of 0
    select the VMEM-budget plan (choose_tiles)."""
    C = gpair.shape[1]
    out = _fused_hist(bins_t, jnp.concatenate(split3(gpair.T), axis=0), pos,
                      node0, n_nodes=n_nodes, n_bin=n_bin, stride=stride,
                      interpret=interpret, row_tile=row_tile,
                      feat_group=feat_group, acc_dtype=jnp.float32)
    out = out.reshape((3, C) + out.shape[1:])  # (3, C, N, F, B)
    return ((out[0] + out[1]) + out[2]).transpose(1, 2, 3, 0)


@functools.partial(
    jax.jit, static_argnames=("node0", "n_nodes", "n_bin", "interpret",
                              "stride", "row_tile", "feat_group")
)
def build_histogram_pallas(bins, gpair, pos, *, node0: int, n_nodes: int,
                           n_bin: int, interpret=None, stride: int = 1,
                           row_tile: int = 0, feat_group: int = 0):
    """hist (n_nodes, F, B, 2) — drop-in for ops/histogram.build_histogram:
    ``onepass_histogram`` from the page as the grower holds it, transposed
    here (the kernels' tests and chip_smoke.py; a round hands the kernel a
    copy transposed once).

    bins (R_pad, F) int (sentinel == n_bin for missing), gpair (R_pad, 2) f32,
    pos (R_pad,) int32.  An explicit feature group compiles only where
    choose_tiles' rule holds, and runs anywhere in interpret mode.
    """
    return onepass_histogram(bins.T, gpair[:, :2], pos, node0,
                             n_nodes=n_nodes, n_bin=n_bin, stride=stride,
                             interpret=interpret, row_tile=row_tile,
                             feat_group=feat_group)


@functools.partial(
    jax.jit, static_argnames=("node0", "n_nodes", "n_bin", "interpret",
                              "stride", "row_tile", "feat_group")
)
def build_histogram_pallas_q(bins, gq, pos, *, node0: int, n_nodes: int,
                             n_bin: int, interpret=None,
                             stride: int = 1, row_tile: int = 0,
                             feat_group: int = 0):
    """Quantised Pallas histogram: (n_nodes, F, B, C, 3) int32 — drop-in for
    ops/quantise.hist_accumulate_q on TPU.  int8 one-hot x int8 limb operand
    -> int32 MXU accumulation: integer partial sums are exact and
    associative, so the output is bitwise identical for ANY grid order or
    topology.

    gq (R_pad, C, 3) int8 signed base-256 limbs (ops/quantise.quantise_gpair).
    """
    R, C, L = gq.shape
    hist = _fused_hist(bins.T, gq.reshape(R, C * L).T, pos, node0,
                       n_nodes=n_nodes, n_bin=n_bin, stride=stride,
                       interpret=interpret, row_tile=row_tile,
                       feat_group=feat_group, acc_dtype=jnp.int32)
    # (C*L, N, F, B) -> (N, F, B, C, L)
    return hist.transpose(1, 2, 3, 0).reshape(
        n_nodes, bins.shape[1], n_bin, C, L)
