"""What a level's histogram costs on the chip as one bfloat16 MXU pass: the
probe that PR 37's go/no-go rests on (PERF.md §5 "A level by its width",
§6 PR 37).

    chiprun -- python scripts/probe_onepass_hist.py [--rows N] [--nodes 1,16]
    python scripts/probe_onepass_hist.py --describe          # compile only, for a described v5e
    JAX_PLATFORMS=cpu python scripts/probe_onepass_hist.py --rows 6000 --allow-cpu   # rehearse

A page of ``--rows`` x ``--cols`` int16 bins under ``--bins`` bins, the root
(one node) and the left children of a level of 16 built nodes (stride 2, a
traced ``node0``, as ``level_step_padded`` asks):

xla     the float32 one-hot matmul at ``HIGHEST`` as it stands
        (``ops/histogram.py:_hist_chunk``), its 2,048-row chunks sliced from
        the page inside a loop with a traced trip count: the best-first
        pass's loop over the page, which compiles in seconds where the
        ``lax.scan`` over 5,126 chunks takes five minutes (``--scan`` adds
        the scan itself, ``build_histogram_at``).
jnp3    the same chunk with the exact three-term split written in ``jnp``:
        a bfloat16 one-hot, a ``(T, 3*2N)`` bfloat16 operand, one dot at
        default precision: whether XLA alone moves.
kernel  ``ops/hist_pallas.py:onepass_histogram`` over the transposed page,
        at the row tiles of ``--tiles``; ``+T`` with the page's transpose
        inside the timed program, ``transpose`` the transpose alone.

Times are host clock around a drained call, the least of ``--reps``; every
form is held against ``xla`` (largest gap over the largest sum).
"""
import argparse
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--rows", type=int, default=10_500_000)
ap.add_argument("--cols", type=int, default=28)
ap.add_argument("--bins", type=int, default=256)
ap.add_argument("--nodes", default="1,16")
ap.add_argument("--tiles", default="512,1024,2048")
ap.add_argument("--only", default="xla,jnp3,kernel,kernel+T,transpose")
ap.add_argument("--scan", action="store_true")
ap.add_argument("--reps", type=int, default=3)
ap.add_argument("--describe", action="store_true")
ap.add_argument("--allow-cpu", action="store_true")
args = ap.parse_args()
if args.describe:
    os.environ.update(JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
if args.describe or args.allow_cpu:
    os.environ["XTB_HIST_IMPL"] = "matmul"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from xgboost_tpu.ops import hist_pallas as HP  # noqa: E402
from xgboost_tpu.ops import histogram as H  # noqa: E402

R, F, B, T = args.rows, args.cols, args.bins, 2048
ONLY = args.only.split(",")
INTERPRET = None if not args.describe else False


def page_loop(chunk_sum, bins, gpair, pos, acc):
    """``acc`` + ``chunk_sum`` over the page's 2,048-row windows, the last
    moved back and masked: ``build_histogram_listed``'s loop over the page."""
    lane = jnp.arange(T, dtype=jnp.int32)

    def body(i, acc):
        start = jnp.minimum(i * T, R - T)
        p = lax.dynamic_slice(pos, (start,), (T,))
        return acc + chunk_sum(
            lax.dynamic_slice(bins, (start, 0), (T, F)),
            lax.dynamic_slice(gpair, (start, 0), (T, 2)),
            jnp.where(start + lane >= i * T, p, -1))

    # a traced trip count: a dynamic loop, which compiles in seconds
    return lax.fori_loop(0, jnp.int32(-(-R // T)) + pos[0] * 0, body, acc)


def xla_form(bins, gpair, pos, node0, *, n_nodes, stride):
    return page_loop(
        lambda b, g, p: H._hist_chunk(b, g, p, node0, n_nodes, B, stride),
        bins, gpair, pos, jnp.zeros((n_nodes, F, B, 2), jnp.float32))


def jnp3_form(bins, gpair, pos, node0, *, n_nodes, stride):
    def chunk_sum(b, g, p):
        mask = p[:, None] == node0 + stride * jnp.arange(n_nodes,
                                                         dtype=p.dtype)
        terms = jnp.stack(HP.split3(g), axis=1)  # (T, 3, 2) bfloat16
        gm = jnp.where(mask[:, None, :, None], terms[:, :, None, :],
                       jnp.zeros((), jnp.bfloat16)).reshape(T, 3 * n_nodes * 2)
        return jnp.dot(H._onehot_feature_major(b, B, jnp.bfloat16), gm,
                       preferred_element_type=jnp.float32)  # (F*B, 3*N*2)

    out = page_loop(chunk_sum, bins, gpair, pos,
                    jnp.zeros((F * B, 3 * n_nodes * 2), jnp.float32))
    out = out.reshape(F, B, 3, n_nodes, 2)
    return ((out[:, :, 0] + out[:, :, 1]) + out[:, :, 2]).transpose(2, 0, 1, 3)


def programs(n_nodes, stride):
    kw = dict(n_nodes=n_nodes, stride=stride)
    out = {}
    if "xla" in ONLY:
        out["xla"] = (lambda b, bt, g, p, n0: xla_form(b, g, p, n0, **kw))
    if args.scan:
        out["xla-scan"] = (lambda b, bt, g, p, n0: H.build_histogram_at.
                           __wrapped__(b, g, p, n0, n_bin=B, **kw))
    if "jnp3" in ONLY:
        out["jnp3"] = (lambda b, bt, g, p, n0: jnp3_form(b, g, p, n0, **kw))
    for tile in (int(t) for t in args.tiles.split(",")):
        def kernel(bt, g, p, n0, tile=tile):
            return HP.onepass_histogram(bt, g, p, n0, n_bin=B, row_tile=tile,
                                        interpret=INTERPRET, **kw)
        if "kernel" in ONLY:
            out[f"kernel T={tile}"] = (
                lambda b, bt, g, p, n0, k=kernel: k(bt, g, p, n0))
        if "kernel+T" in ONLY:
            out[f"kernel+T T={tile}"] = (
                lambda b, bt, g, p, n0, k=kernel: k(b.T, g, p, n0))
    if "transpose" in ONLY:
        out["transpose"] = lambda b, bt, g, p, n0: b.T
    return {k: jax.jit(v) for k, v in out.items()}


def main():
    print(f"{R} x {F}, B {B}: one-hot {F * B} rows a chunk", flush=True)
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def shape(s, d):
            return jax.ShapeDtypeStruct(s, d, sharding=chip)

        operands = (shape((R, F), jnp.int16), shape((F, R), jnp.int16),
                    shape((R, 2), jnp.float32), shape((R,), jnp.int32),
                    shape((), jnp.int32))
    else:
        platform = jax.devices()[0].platform
        print("device", platform, jax.devices()[0].device_kind, flush=True)
        if platform != "tpu" and not args.allow_cpu:
            sys.exit("no TPU: this probe times the chip (--allow-cpu rehearses)")
        ks = jax.random.split(jax.random.key(0), 3)
        bins = jax.jit(lambda: jax.random.randint(
            ks[0], (R, F), 0, B, jnp.int32).astype(jnp.int16))()
        bins_t = jax.block_until_ready(jax.jit(lambda b: b.T)(bins))
        gpair = jax.random.normal(ks[1], (R, 2), jnp.float32)
    for n_nodes in (int(v) for v in args.nodes.split(",")):
        # the root, or the left children of the level of 2*n_nodes slots
        stride = 1 if n_nodes == 1 else 2
        node0 = 0 if n_nodes == 1 else 2 * n_nodes - 1
        if not args.describe:
            pos = jax.random.randint(ks[2], (R,), node0,
                                     node0 + stride * n_nodes, jnp.int32)
            operands = (bins, bins_t, gpair, pos, jnp.int32(node0))
        got = {}
        for name, fn in programs(n_nodes, stride).items():
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(*operands).compile()
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                print(f"nodes {n_nodes:>3} {name:<18} refused: "
                      + str(e)[:300].replace("\n", " | "), flush=True)
                continue
            line = (f"nodes {n_nodes:>3} {name:<18} compile "
                    f"{time.perf_counter() - t0:6.1f} s  temp "
                    f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:8.1f} MB")
            if not args.describe:
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    got[name] = jax.block_until_ready(compiled(*operands))
                    times.append(time.perf_counter() - t0)
                line += "  run " + " ".join(f"{t:.4f}" for t in times) + " s"
            print(line, flush=True)
        want = got.get("xla")
        for name, h in got.items():
            if want is not None and name not in ("xla", "transpose"):
                print(f"nodes {n_nodes:>3} {name:<18} against xla: largest gap "
                      f"{float(jnp.max(jnp.abs(h - want))):.3e} of "
                      f"{float(jnp.max(jnp.abs(want))):.3e}", flush=True)


if __name__ == "__main__":
    main()
