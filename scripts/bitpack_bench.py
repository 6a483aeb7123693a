"""Bit-packed Ellpack experiment (VERDICT r2 missing #4 / next #7).

The reference packs bin indices to ceil(log2(n_bins)) bits in HBM
(src/common/compressed_iterator.h, src/data/ellpack_page.cuh:26); this repo
stores u8/u16.  Question: would 4-bit packing (max_bin<=16) pay on the TPU
hist kernel?

Measures build_histogram at max_bin 256/64/16 with (a) the resident u8
layout and (b) a simulated 4-bit packed layout (two bins per byte, unpacked
with shift/mask on the fly before the one-hot matmul — exactly what a
packed kernel would do).  Run on CPU XLA for the shape of the answer and on
the TPU chip (python scripts/bitpack_bench.py, no JAX_PLATFORMS override)
for the real number; results go into docs/bitpack.md.
"""
import functools
import json
import sys
import time

import jax

if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from xgboost_tpu.ops.histogram import _hist_accumulate  # noqa: E402
from xgboost_tpu.ops.histogram import build_histogram  # noqa: E402

R, F = 1 << 20, 28
N_NODES = 8


def timed(fn, reps: int = 5) -> float:
    """Median wall seconds of fn() with device completion; one warmup call
    first so compile time never lands in the samples."""
    jax.block_until_ready(fn())  # compile/warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _unpack4(packed):
    """(R, F/2) u8 -> (R, F) u8: two 4-bit bins per byte."""
    lo = packed & 0xF
    hi = packed >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("n_bin",))
def _packed_hist(packed, gp, pos, *, n_bin):
    """ONE XLA program: unpack fused ahead of the one-hot matmul — what a
    packed kernel would do (no (R, F) u8 round-trip through HBM)."""
    return _hist_accumulate(_unpack4(packed), gp, pos, 0, N_NODES, n_bin,
                            2048, 1)


def native_section(rng):
    """Round-7 re-measurement (docs/bitpack.md): the scalar 2026-07 numbers
    could not answer what a VECTOR unpack does to the packed-4-bit
    roofline.  This times the native row-sweep hist kernel (the production
    CPU path since the FFI revival) on the resident u8 layout vs the
    packed two-bins-per-byte layout whose nibble unpack is fused into the
    AVX2 index-prep (native/xtb_simd.h xtb_hist_sweep_p4_avx2), at both
    simd levels, nthread=1 (the per-core roofline the decision is about).
    """
    from xgboost_tpu.utils import native

    lib = native.load_native()
    if lib is None:
        return {"native": "unavailable"}
    out = {"simd": native.simd_info()}
    native.set_nthread(1)
    gp = np.ascontiguousarray(rng.normal(size=(R, 2)), np.float32)
    pos = np.ascontiguousarray(rng.integers(0, N_NODES, size=R), np.int32)

    for B in (256, 16):
        bins = np.ascontiguousarray(
            rng.integers(0, B, size=(R, F)), np.uint8)
        hist = np.empty((N_NODES, F, B, 2), np.float32)

        def u8():
            lib.xtb_hist_f32_u8(bins.ctypes.data, gp.ctypes.data,
                                pos.ctypes.data, R, F, B, 0, N_NODES, 1, 2,
                                hist.ctypes.data)

        for level in ("scalar", "auto"):
            native.set_simd(level)
            out[f"native_u8_B{B}_{level}_s"] = round(timed(u8), 5)
        if B <= 16:
            packed = np.ascontiguousarray(
                bins[:, 0::2] | (bins[:, 1::2] << 4))
            hist_p = np.empty_like(hist)

            def p4():
                lib.xtb_hist_packed4(packed.ctypes.data, gp.ctypes.data,
                                     pos.ctypes.data, R, F, B, 0, N_NODES,
                                     1, hist_p.ctypes.data)

            for level in ("scalar", "auto"):
                native.set_simd(level)
                out[f"native_packed4_B{B}_{level}_s"] = round(timed(p4), 5)
            np.testing.assert_array_equal(hist_p, hist)  # layouts agree
            vec = out[f"native_u8_B{B}_auto_s"]
            out[f"native_packed4_B{B}_vector_speedup"] = round(
                vec / out[f"native_packed4_B{B}_auto_s"], 3)
    native.set_simd("auto")
    native.set_nthread(0)
    return out


def main():
    rng = np.random.default_rng(0)
    gp = jnp.asarray(rng.normal(size=(R, 2)).astype(np.float32))
    pos = jnp.asarray(rng.integers(0, N_NODES, size=R).astype(np.int32))
    results = {"platform": jax.devices()[0].platform, "rows": R,
               "features": F, "n_nodes": N_NODES}
    for B in (256, 64, 16):
        bins_np = rng.integers(0, B, size=(R, F)).astype(np.uint8)
        bins = jnp.asarray(bins_np)
        t_u8 = timed(lambda: build_histogram(
            bins, gp, pos, node0=0, n_nodes=N_NODES, n_bin=B))
        results[f"u8_B{B}_s"] = round(t_u8, 5)
        if B <= 16:
            packed_np = (bins_np[:, 0::2] | (bins_np[:, 1::2] << 4))
            packed = jnp.asarray(packed_np)
            t_p4 = timed(lambda: _packed_hist(packed, gp, pos, n_bin=B))
            results[f"packed4_B{B}_s"] = round(t_p4, 5)
            results[f"packed4_B{B}_speedup"] = round(t_u8 / t_p4, 3)
        # HBM-traffic roofline: bins bytes per level vs matmul FLOPs
        results[f"flops_per_bins_byte_B{B}"] = 2 * B * N_NODES * 2
    if jax.devices()[0].platform == "cpu":
        results.update(native_section(rng))
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
