"""Fixed-point gradient quantisation — order-invariant histogram sums.

The role of the reference's GradientQuantiser (src/tree/gpu_hist/
quantiser.cuh:52): there, gradients become int64 fixed-point so that atomic
adds and the NCCL allreduce are EXACT integer sums, making gpu_hist bitwise
reproducible across any worker/GPU topology.  The default path here gets
per-topology determinism from fixed-order f32 accumulation, but f32 sums
change bits when the REDUCTION SHAPE changes (4-chip psum vs 1-chip scan),
so deep near-tie splits can flip across topologies.

TPU-native equivalent: quantise (g, h) to 22-bit signed fixed point against
a global per-round scale, split each value into three signed int8 limbs
(base 256), and build the histogram as int8 x int8 -> int32 matmuls — the
MXU's native integer path.  Integer partial sums are exact and associative,
so chunk order, chip count (lax.psum over int32), and process count (host
int64 allreduce) all produce identical bits; the one rounding step is a
single deterministic elementwise dequantise AFTER all reductions.

Budget proof (why this is exact):
 - |q| <= 2**22 - 1, so limb 2 after the two base-256 extractions lies in
   [-65, 65] — comfortably int8;
 - a limb-histogram entry accumulates at most R * 128 on device, int32-safe
   up to R = 2**24 (16.7M) rows PER PROCESS — covering the 11M-row HIGGS
   ladder with headroom; every quantised grower entry calls
   ``check_row_budget`` before accumulating, so overflow raises instead of
   wrapping;
 - the cross-process reduction runs (and stays) in int64 on host — no
   global row bound — and ``dequantise`` applies the same elementwise f32
   formula to either limb width, so every topology shares one rounding step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# 22-bit signed fixed point: limb decomposition stays int8-safe (see proof
# above) and resolution 2**-22 of the max-gradient scale sits at f32's own
# mantissa floor, so no training-visible precision is lost vs the f32 path.
QUANT_BITS = 22
_QMAX = float((1 << QUANT_BITS) - 1)
# int32 limb-accumulator budget: R_global * 128 must stay below 2**31
MAX_ROWS = 1 << 24


@jax.jit
def local_rho(gpair, valid):
    """Per-channel max |gradient| over valid rows: (C,) f32.

    max is associative/idempotent, so psum-max across chips and host MAX
    allreduce across processes reproduce the same value on every topology
    (the reference derives its scale from global sums the same way,
    quantiser.cuh:23 via InitRoot's allreduce).
    """
    g = jnp.abs(gpair) * valid[:, None].astype(gpair.dtype)
    return jnp.max(g, axis=0)


@jax.jit
def quantise_gpair(gpair, rho):
    """(R, C) f32 -> (R, C, 3) int8 signed base-256 limbs of the fixed-point
    gradient q = round(g / rho * (2**22 - 1))."""
    scale = _QMAX / jnp.maximum(rho, 1e-30)
    q = jnp.clip(jnp.round(gpair * scale[None, :]), -_QMAX, _QMAX).astype(
        jnp.int32)
    limbs = []
    for _ in range(2):
        l = ((q + 128) & 255) - 128          # signed low limb in [-128, 127]
        limbs.append(l)
        q = (q - l) >> 8                     # exact: q - l divisible by 256
    limbs.append(q)                          # |top| <= 65
    return jnp.stack(limbs, axis=-1).astype(jnp.int8)


def _hist_chunk_q(bins_c, gq_c, pos_c, node0, n_nodes: int, n_bin: int,
                  stride: int = 1):
    """One row-chunk's int32 limb histogram: (N, F, B, C, 3).

    Same masked one-hot matmul as the f32 kernel (histogram.py:_hist_chunk)
    but in int8 operands with int32 accumulation — exact, and on TPU the
    MXU's int8 path, so determinism costs no matmul throughput.
    """
    from .histogram import _onehot_feature_major

    T, F = bins_c.shape
    C, L = gq_c.shape[1], gq_c.shape[2]
    onehot = _onehot_feature_major(bins_c, n_bin, jnp.int8)
    nodemask = (pos_c[:, None]
                == (node0 + stride * jnp.arange(n_nodes, dtype=pos_c.dtype))
                ).astype(jnp.int8)  # (T, N)
    # (T, N*C*L) — int8 product of a 0/1 mask and a limb is the limb
    gm = (nodemask[:, :, None] * gq_c.reshape(T, 1, C * L)).reshape(
        T, n_nodes * C * L)
    out = jnp.dot(onehot, gm, preferred_element_type=jnp.int32)
    return out.reshape(F, n_bin, n_nodes, C, L).transpose(2, 0, 1, 3, 4)


def hist_accumulate_q(bins, gq, pos, node0, n_nodes: int, n_bin: int,
                      chunk: int = 2048, stride: int = 1):
    """Chunked exact int32 limb-histogram accumulation (any chunk order
    produces identical bits — integer addition is associative)."""
    from .histogram import _host_impl, scatter_hist_driver

    impl = _host_impl()
    if impl == "native":
        # native int32 limb row pass (native/xtb_kernels.h xtb_hist_q):
        # exactness makes the accumulation order irrelevant, so the
        # deterministic contract rides the same kernel speed as f32
        import numpy as np

        from ..utils import native

        native.ensure_pool()
        R, F = bins.shape
        C, L = gq.shape[1], gq.shape[2]
        b = bins
        if b.dtype not in (jnp.uint8, jnp.uint16, jnp.int16, jnp.int32):
            b = b.astype(jnp.int32)
        call = jax.ffi.ffi_call(
            "xtb_hist_q",
            jax.ShapeDtypeStruct((n_nodes, F, n_bin, C * L), jnp.int32))
        flat = call(b, gq.reshape(R, C * L), pos.astype(jnp.int32),
                    jnp.asarray(node0, jnp.int32).reshape(1),
                    stride=np.int32(stride))
        return flat.reshape(n_nodes, F, n_bin, C, L)
    if impl == "scatter":
        C, L = gq.shape[1], gq.shape[2]
        flat = scatter_hist_driver(
            bins, gq.reshape(gq.shape[0], C * L).astype(jnp.int32), pos,
            node0, n_nodes, n_bin, stride, C * L, jnp.int32)
        return flat.reshape(flat.shape[:3] + (C, L))
    R, F = bins.shape
    if R <= chunk:
        return _hist_chunk_q(bins, gq, pos, node0, n_nodes, n_bin, stride)
    n_chunks = R // chunk
    rem = R - n_chunks * chunk

    def body(acc, xs):
        b, g, p = xs
        return acc + _hist_chunk_q(b, g, p, node0, n_nodes, n_bin, stride), None

    # carry seeded with chunk 0: under shard_map the contributions vary
    # over the data axis and the scan carry type must match (histogram.py
    # _hist_accumulate has the same rule)
    C, L = gq.shape[1], gq.shape[2]
    acc0 = _hist_chunk_q(bins[:chunk], gq[:chunk], pos[:chunk], node0,
                         n_nodes, n_bin, stride)
    xs = (bins[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk, F),
          gq[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk, C, L),
          pos[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk))
    acc, _ = lax.scan(body, acc0, xs)
    if rem:
        acc = acc + _hist_chunk_q(bins[-rem:], gq[-rem:], pos[-rem:], node0,
                                  n_nodes, n_bin, stride)
    return acc


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bin", "chunk",
                                             "stride"))
def build_histogram_q(bins, gq, pos, node0, *, n_nodes: int, n_bin: int,
                      chunk: int = 2048, stride: int = 1):
    """Traced-node0 quantised histogram build: (N, F, B, C, 3) int32."""
    node0 = jnp.asarray(node0, jnp.int32)
    return hist_accumulate_q(bins, gq, pos, node0, n_nodes, n_bin, chunk,
                             stride)


@jax.jit
def node_sums_q(gq, pos, node0, n_nodes_arr):
    """Per-node quantised gradient totals: (N, C, 3) int32 — exact.

    n_nodes_arr is a length-N arange (static shape carrier); node ids are
    node0 + that range.
    """
    nodemask = (pos[:, None]
                == (node0 + n_nodes_arr)[None, :]).astype(jnp.int8)
    C, L = gq.shape[1], gq.shape[2]
    out = jnp.dot(nodemask.T, gq.reshape(gq.shape[0], C * L),
                  preferred_element_type=jnp.int32)
    return out.reshape(-1, C, L)


@jax.jit
def dequantise(hist_q, rho):
    """int32 limb sums -> f32 values: THE one rounding step, applied after
    every reduction so all topologies share this exact compiled formula.

    hist_q: (..., C, 3) int32;  rho: (C,) f32.
    """
    f = hist_q.astype(jnp.float32)
    combined = f[..., 0] + 256.0 * f[..., 1] + 65536.0 * f[..., 2]
    return combined * (rho / _QMAX)


def quantised_root_state(state, gq, rho, *, axis_name=None,
                         process_reduce: bool = False):
    """Replace the f32 root totals with the exactly-reduced quantised root
    sum (InitRoot + GlobalSum, updater_gpu_hist.cu:581, in fixed point):
    f32 root sums change bits with the reduction shape, quantised ones
    cannot."""
    root = node_sums_q(gq, state.pos, jnp.int32(0),
                       jnp.arange(1, dtype=jnp.int32))
    if axis_name is not None:
        root = jax.lax.psum(root, axis_name)
    if process_reduce:
        root = allreduce_limbs(root)
    totals0 = dequantise(root, rho)[0]
    return state._replace(totals=state.totals.at[0].set(totals0))


def check_row_budget(n_rows: int) -> None:
    """Enforce the int32 limb-accumulator budget BEFORE any device
    accumulation can wrap: per-process padded rows x 128 must stay below
    2**31.  Called by every quantised grower entry point."""
    if n_rows > MAX_ROWS:
        raise ValueError(
            f"deterministic_histogram supports up to {MAX_ROWS} rows per "
            f"process (int32 limb-accumulator budget); got {n_rows}.  Shard "
            "rows over more processes, or use the default f32 histogram.")


def prepare_quantised(gpair, valid, state, *, distributed: bool = False,
                      axis_name=None):
    """The shared quantised-training entry sequence used by every grower
    flavour (single-chip, shard_map mesh, process, streaming): row-budget
    check, global per-channel scale (chip max via GSPMD/psum is exact;
    process max via host MAX allreduce), gradient limb quantisation, and
    the exactly-reduced root totals.  Returns (gq, rho, state).
    """
    check_row_budget(gpair.shape[0])
    rho = local_rho(gpair, valid)
    if axis_name is not None:
        rho = jax.lax.pmax(rho, axis_name)
    if distributed:
        import numpy as np

        from .. import collective

        rho = jnp.asarray(collective.allreduce(np.asarray(rho),
                                               collective.Op.MAX))
    gq = quantise_gpair(gpair, rho)
    state = quantised_root_state(state, gq, rho, axis_name=axis_name,
                                 process_reduce=distributed)
    return gq, rho, state


def allreduce_limbs(hist_q) -> "jnp.ndarray":
    """Cross-process exact limb reduction: gather int32 limbs, sum in int64
    on host (order-free), and hand the int64 limbs back — dequantise casts
    each limb to f32 the same way for either width, so every topology still
    shares one rounding formula.  The role of the reference's integer NCCL
    allreduce (quantiser.cuh + comm.cuh AllReduce<kInt64>)."""
    import numpy as np

    from .. import collective

    return jnp.asarray(collective.allreduce(
        np.asarray(hist_q).astype(np.int64)))
