"""What a level's histogram costs on the chip with its one-hot a column's bins
tall, and what bringing the columns into tier order costs: the probe that
PR 35 chose the in-scan permutation from (PERF.md §6, PR 35).

    chiprun -- python scripts/probe_bin_tiers.py [--rows N] [--nodes 16,64]
    python scripts/probe_bin_tiers.py --describe [--rows N]   # compile only, for a described v5e
    JAX_PLATFORMS=cpu python scripts/probe_bin_tiers.py --rows 8192 --allow-cpu   # rehearse

A page of ``--rows`` x 968 int16 bins in ``--signature``'s tiers (bosch's),
four fifths of its entries the sentinel, the left children of a level of
``--nodes`` built nodes (stride 2, a traced ``node0``, as
``level_step_padded`` asks):

one     ``build_histogram_at`` without tiers: the parent's level.
scan    with tiers, as shipped: the chunk's columns gathered into tier
        order inside the scan (``_hist_chunk``), the tiers put back once.
stored  with tiers over a page that is stored in tier order: no gather in
        the scan (what a permuted ``build_ellpack`` would buy).
page    the whole page gathered into tier order before the scan, a level.
untier  the way back alone: pad, concatenate, gather by column.

Times are host clock around a drained call, the least of ``--reps``.
"""
import argparse
import itertools
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--rows", type=int, default=946_176)
ap.add_argument("--nodes", default="16,64")
ap.add_argument("--signature", default="32:480,64:128,128:176,256:184")
ap.add_argument("--only", default="one,scan,stored,page,untier")
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from xgboost_tpu.ops import histogram as H  # noqa: E402

WIDTHS = tuple(tuple(int(v) for v in t.split(":"))
               for t in args.signature.split(","))
R, B, T = args.rows, WIDTHS[-1][0], 2048
F = sum(n for _, n in WIDTHS)
assert R % T == 0, "whole chunks only"
ONLY = args.only.split(",")


def stored_scan(bins, gpair, pos, node0, *, n_nodes):
    """``_hist_accumulate`` over a page whose columns already stand tier
    after tier: the shipped chunk less its gather."""
    ends = list(itertools.accumulate(n for _, n in WIDTHS))

    def part(b, g, p):
        mask = (p[:, None] == node0 + 2 * jnp.arange(n_nodes, dtype=p.dtype))
        gm = (mask.astype(jnp.float32)[:, :, None] * g[:, None, :]).reshape(
            T, n_nodes * 2)
        return tuple(
            jnp.dot(H._onehot_feature_major(b[:, hi - n:hi], w, jnp.float32),
                    gm, preferred_element_type=jnp.float32,
                    precision=H._EXACT_F32)
            .reshape(n, w, n_nodes, 2).transpose(2, 0, 1, 3)
            for (w, n), hi in zip(WIDTHS, ends))

    n = R // T
    acc, _ = lax.scan(
        lambda acc, xs: (H._add(acc, part(*xs)), None),
        part(bins[:T], gpair[:T], pos[:T]),
        (bins[T:].reshape(n - 1, T, F), gpair[T:].reshape(n - 1, T, 2),
         pos[T:].reshape(n - 1, T)))
    return acc


def programs(n_nodes, tiers):
    at = H.build_histogram_at.__wrapped__
    out = {
        "one": lambda b, g, p, n0, t: at(b, g, p, n0, n_nodes=n_nodes,
                                         n_bin=B, stride=2),
        "scan": lambda b, g, p, n0, t: at(b, g, p, n0, n_nodes=n_nodes,
                                          n_bin=B, stride=2, tiers=t),
        "stored": lambda b, g, p, n0, t: H._untier(
            stored_scan(b, g, p, n0, n_nodes=n_nodes), t, B),
        "page": lambda b, g, p, n0, t: H._untier(
            stored_scan(b[:, t.order], g, p, n0, n_nodes=n_nodes), t, B),
        "untier": lambda b, g, p, n0, t: H._untier(
            tuple(jnp.full((n_nodes, n, w, 2), g[0, 0]) for w, n in WIDTHS),
            t, B),
    }
    return {k: jax.jit(v) for k, v in out.items() if k in ONLY}


def main():
    rng = np.random.default_rng(0)
    n_bins = np.concatenate([np.full(n, w) for w, n in WIDTHS])
    order = rng.permutation(F)
    n_bins = n_bins[np.argsort(order)]  # column order[j] sits j-th by tier
    tiers = H.bin_tiers(n_bins, B)
    assert tiers.widths == WIDTHS, (tiers.widths, WIDTHS)
    tall = H.onehot_rows(tiers, B, F)
    print(f"{R} x {F}, B {B}, tiers {WIDTHS}: one-hot rows {tall} of {F * B} "
          f"({100 * tall / (F * B):.1f}%)", flush=True)
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def shape(s, d):
            return jax.ShapeDtypeStruct(s, d, sharding=chip)

        operands = (shape((R, F), jnp.int16), shape((R, 2), jnp.float32),
                    shape((R,), jnp.int32), shape((), jnp.int32),
                    H.BinTiers(WIDTHS, shape((F,), jnp.int32)))
    else:
        platform = jax.devices()[0].platform
        print("device", platform, jax.devices()[0].device_kind, flush=True)
        if platform != "tpu" and not args.allow_cpu:
            sys.exit("no TPU: this probe times the chip (--allow-cpu rehearses)")
        key = jax.random.key(0)
        ks = jax.random.split(key, 4)
        bins = jax.jit(lambda: jnp.where(
            jax.random.uniform(ks[0], (R, F)) < 0.8, B,
            (jax.random.uniform(ks[1], (R, F))
             * jnp.asarray(n_bins, jnp.float32)).astype(jnp.int32)).astype(
                 jnp.int16))()
        gpair = jax.random.normal(ks[2], (R, 2), jnp.float32)
    for n_nodes in (int(v) for v in args.nodes.split(",")):
        node0 = 2 * n_nodes - 1  # the level of 2*n_nodes slots
        if not args.describe:
            pos = jax.random.randint(ks[3], (R,), node0, node0 + 2 * n_nodes,
                                     jnp.int32)
            operands = (bins, gpair, pos, jnp.int32(node0), tiers)
        got = {}
        for name, fn in programs(n_nodes, tiers).items():
            t0 = time.perf_counter()
            compiled = fn.lower(*operands).compile()
            line = (f"nodes {n_nodes:>3} {name:<7} compile "
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
        want = got.get("one")
        for name, h in got.items():
            if want is not None and name in ("scan", "stored", "page"):
                if name == "stored":  # its page is read as if in tier order
                    continue
                print(f"nodes {n_nodes:>3} {name:<7} against one: largest gap "
                      f"{float(jnp.max(jnp.abs(h - want))):.3e} of "
                      f"{float(jnp.max(jnp.abs(want))):.3e}", flush=True)


if __name__ == "__main__":
    main()
