"""Pallas TPU histogram kernel — the fused form of ops/histogram.py.

The CUDA reference builds histograms with shared-memory atomics
(src/tree/gpu_hist/histogram.cu:37-120).  TPU has no atomics; the masked
one-hot matmul formulation (ops/histogram.py) is MXU-shaped, and XLA keeps the
one-hot operand out of HBM only in the feature-major form written there (what
the chip runs by default).  This kernel builds the one-hot in VMEM by hand, so
HBM sees only: bins read once (R*F*itemsize bytes), the gradient operand read
once per feature group, histogram written once.

Layout — rows ride the 128-lane axis everywhere, so every block is
lane-dense and no value is ever sliced off the lane axis:
  inputs (transposed once per call by the wrapper):
      bins_t (F, R) int, vals_t (C, R) f32|int8, pos (1, R) int32
  grid = (F/FG feature groups, R/T row tiles)   [both arbitrary/sequential]
  per step: bins tile (FG, T) + operand tile (C, T) + pos tile (1, T) in VMEM
  out block (FG, C*N, B) stays VMEM-resident across the row-tile loop of one
  feature group (index_map ignores the row index) and accumulates
      hist[f] += gm @ onehot(bins_t[f]).T            # (C*N, T) x (B, T)^T
  where gm[c*N + n] = vals_t[c] masked to the rows sitting in node n.

One kernel body serves the float32 form (C=2 channels g,h; f32 accumulate
pinned to ``Precision.HIGHEST``: the one-hot operand is exact in bf16 but
the gradient operand is not, and a default-precision f32 MXU matmul rounds
it to 8 mantissa bits) and the quantised form (C=6 int8 limbs; int32
accumulate, exact and order-free — the reference's GradientQuantiser
contract, quantiser.cuh:52).

Determinism: sequential grid, fixed accumulation order, no atomics.
"""
from __future__ import annotations

import functools

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


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def choose_tiles(n_features: int, n_bin: int, n_nodes: int,
                 bin_itemsize: int = 1,
                 vmem_budget: int = _VMEM_BUDGET, out_ch: int = 2) -> tuple:
    """Pick (row_tile, feat_group) that fits the VMEM budget.

    The feature group is not free: Mosaic takes a (FG, T) block of the
    (F, R) bins only when FG is the whole feature axis or a multiple of the
    dtype's sublane tile, so FG = F for narrow data and 32 otherwise.  The
    row tile is the largest of 1024..256 whose working set fits (on a v5e
    at 2M x 28 x 256 bins a 2048-row tile took 2.5 to 4 times as long to
    compile as a 1024-row one and ran the float32 form 15% slower; 512 and
    1024 ran alike — smoke timings of PR 21, see PERF.md):
      - out block, double-buffered: 2 * FG * roundup(out_ch*N, 8) * B_pad * 4
        (out_ch = 2 for the f32 (g,h) kernel, 6 for the (g,h) x 3-limb one)
      - double-buffered inputs: 2 * T * (FG*itemsize + 8*4 + 8*4)
      - scratch: widened bins FG*T*4, one-hot B_pad*T*4 (one feature at a
        time), masked operand and its iota temporaries 3 * out_ch*N*T*4
    Always returns something; the compiler refuses what does not fit.
    """
    fg = n_features if n_features <= _FEAT_GROUP else _FEAT_GROUP
    fg = max(fg, 1)
    m = _round_up(out_ch * n_nodes, 8)
    b_pad = _round_up(n_bin, _LANES)
    out_b = 2 * fg * m * b_pad * 4
    for t in (1024, 512, 256):
        in_b = 2 * t * (fg * bin_itemsize + 64)
        scratch = fg * t * 4 + b_pad * t * 4 + 3 * m * t * 4
        if out_b + in_b + scratch <= vmem_budget:
            return t, fg
    return 256, fg


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


def _masked_operand(pos_row, vals, *, node0: int, n_nodes: int, stride: int):
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


def _hist_kernel(bins_ref, vals_ref, pos_ref, out_ref, *, node0: int,
                 n_nodes: int, stride: int):
    @pl.when(pl.program_id(1) == 0)  # first row tile of this feature group
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    op_dtype = vals_ref.dtype  # float32 | int8
    quantised = jnp.issubdtype(op_dtype, jnp.integer)
    wide = jnp.int32 if quantised else jnp.float32
    # 0/1 mask times a limb is the limb: the masked operand stays int8-safe
    gm = _masked_operand(pos_ref[...], vals_ref[...].astype(wide),
                         node0=node0, n_nodes=n_nodes,
                         stride=stride).astype(op_dtype)
    bins = bins_ref[...].astype(jnp.int32)  # (FG, T)
    FG, T = bins.shape
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[2], T), 0)
    for f in range(FG):  # static unroll
        # (B, T); the missing sentinel (== n_bin) lands in a pad column or
        # nowhere, never in a real bin
        onehot = (bins[f:f + 1, :] == bin_ids).astype(wide).astype(op_dtype)
        out_ref[f] += jax.lax.dot_general(
            gm, onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),  # contract rows
            preferred_element_type=out_ref.dtype,
            precision=None if quantised else jax.lax.Precision.HIGHEST,
        )


def _fused_hist(bins, vals_t, pos, *, node0: int, n_nodes: int, n_bin: int,
                stride: int, interpret, row_tile: int, feat_group: int,
                acc_dtype):
    """Shared wrapper: (N, F, B, C) from bins (R, F), vals_t (C, R), pos (R,).
    Rows are padded up to the row tile (pad rows carry pos = -1, matching no
    node), features up to the feature group, bins up to the lane width."""
    interpret = _resolve_interpret(interpret)
    R, F = bins.shape
    C = vals_t.shape[0]
    M = C * n_nodes
    T, FG = row_tile, feat_group
    if not (T and FG):
        at, afg = choose_tiles(F, n_bin, n_nodes, bins.dtype.itemsize,
                               out_ch=C)
        T, FG = T or at, FG or afg
    R_pad, F_pad = _round_up(R, T), _round_up(F, FG)
    B_pad = _round_up(n_bin, _LANES)
    bins_t = jnp.pad(bins.T, ((0, F_pad - F), (0, R_pad - R)),
                     constant_values=n_bin)
    vals_t = jnp.pad(vals_t, ((0, 0), (0, R_pad - R)))
    pos_row = jnp.pad(pos.astype(jnp.int32), (0, R_pad - R),
                      constant_values=-1)[None, :]
    n_fg = F_pad // FG

    out = pl.pallas_call(
        functools.partial(_hist_kernel, node0=node0, n_nodes=n_nodes,
                          stride=stride),
        grid=(n_fg, R_pad // T),
        in_specs=[
            pl.BlockSpec((FG, T), lambda fg, i: (fg, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, T), lambda fg, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), lambda fg, i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((FG, M, B_pad), lambda fg, i: (fg, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((F_pad, M, B_pad), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * R_pad * F_pad * B_pad * M,
            bytes_accessed=R_pad * F_pad * bins.dtype.itemsize
            + R_pad * (C * vals_t.dtype.itemsize + 4) * n_fg
            + F_pad * M * B_pad * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(bins_t, vals_t, pos_row)
    # (F_pad, C*N, B_pad) -> (N, F, B, C)
    return out[:F, :, :n_bin].reshape(F, C, n_nodes, n_bin).transpose(
        2, 0, 3, 1)


@functools.partial(
    jax.jit, static_argnames=("node0", "n_nodes", "n_bin", "interpret",
                              "stride", "row_tile", "feat_group")
)
def build_histogram_pallas(bins, gpair, pos, *, node0: int, n_nodes: int,
                           n_bin: int, interpret=None, stride: int = 1,
                           row_tile: int = 0, feat_group: int = 0):
    """hist (n_nodes, F, B, 2) — drop-in for ops/histogram.build_histogram.

    bins (R_pad, F) int (sentinel == n_bin for missing), gpair (R_pad, 2) f32,
    pos (R_pad,) int32.  ``row_tile``/``feat_group`` of 0 select the
    VMEM-budget plan (choose_tiles); an explicit feature group compiles only
    where choose_tiles' rule holds, and runs anywhere in interpret mode.
    """
    return _fused_hist(bins, gpair[:, :2].astype(jnp.float32).T, pos,
                       node0=node0, n_nodes=n_nodes, n_bin=n_bin,
                       stride=stride, interpret=interpret, row_tile=row_tile,
                       feat_group=feat_group, acc_dtype=jnp.float32)


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
    hist = _fused_hist(bins, gq.reshape(R, C * L).T, pos, node0=node0,
                       n_nodes=n_nodes, n_bin=n_bin, stride=stride,
                       interpret=interpret, row_tile=row_tile,
                       feat_group=feat_group, acc_dtype=jnp.int32)
    return hist.reshape(hist.shape[:3] + (C, L))
