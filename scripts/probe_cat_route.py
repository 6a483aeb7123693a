"""What a level's route costs on the chip where its splits are categorical:
the row-sized gather of each row's membership against the dense form
(``tree/grow.py:_membership_dense``), the probe the route's form was chosen
by (PERF.md §5).

    python scripts/probe_cat_route.py [--rows N] [--widths 1,32] [--only dense]   # on a TPU host
    python scripts/probe_cat_route.py --describe [--rows N]   # compile only, for a described v5e
    JAX_PLATFORMS=cpu python scripts/probe_cat_route.py --rows 65536 --allow-cpu   # rehearse

A page of ``--rows`` x 8 int16 bins of ``--bins`` codes (the airline cell's
widest column), every row on the level of ``width`` slots, a third of the
slots' splits categorical with random sets:

gather   ``_update_positions_gather``: a row-sized gather from each table.
dense    ``_update_positions_dense``, as shipped: selects over the nodes and
         the columns, the membership by ``_membership_dense``.
numeric  ``_update_positions_dense`` with no categorical split: what a level
         of HIGGS's route costs at this page's shape.

Times are host clock around a drained call, the least of ``--reps``; every
form's positions are checked equal to the gather's.
"""
import argparse
import os
import sys
import time
import types

ap = argparse.ArgumentParser()
ap.add_argument("--rows", type=int, default=40_000_256)
ap.add_argument("--bins", type=int, default=300)
ap.add_argument("--widths", default="1,32")
ap.add_argument("--only", default="gather,dense,numeric")
ap.add_argument("--reps", type=int, default=3)
ap.add_argument("--describe", action="store_true")
ap.add_argument("--allow-cpu", action="store_true")
args = ap.parse_args()
if args.describe:
    os.environ.update(JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from xgboost_tpu.tree import grow  # noqa: E402

F = 8
R, B = args.rows, args.bins


def tables(width: int, categorical: bool):
    rng = np.random.default_rng(width)
    can = rng.random(width) < 0.9
    return types.SimpleNamespace(
        feature=jnp.asarray(rng.integers(0, F, size=width), jnp.int32),
        bin=jnp.asarray(rng.integers(0, B, size=width), jnp.int32),
        default_left=jnp.asarray(rng.random(width) < 0.5),
        is_cat=jnp.asarray(rng.random(width) < (0.34 if categorical else 0)),
        cat_set=jnp.asarray(rng.random((width, B)) < 0.5)), jnp.asarray(can)


def forms(width: int):
    node0 = width - 1

    def make(form, categorical):
        best, can = tables(width, categorical)

        def run(bins, pos):
            return form(bins, pos, best, can, node0, width, B, categorical)
        return jax.jit(run)

    made = {"gather": (grow._update_positions_gather, True),
            "dense": (grow._update_positions_dense, True),
            "numeric": (grow._update_positions_dense, False)}
    return {name: make(*made[name]) for name in args.only.split(",")}


def main():
    print(f"{R} x {F} int16, {B} bins", flush=True)
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        operands = lambda width: (  # noqa: E731
            jax.ShapeDtypeStruct((R, F), jnp.int16, sharding=chip),
            jax.ShapeDtypeStruct((R,), jnp.int32, sharding=chip))
    else:
        platform = jax.devices()[0].platform
        print("device", platform, jax.devices()[0].device_kind, flush=True)
        if platform != "tpu" and not args.allow_cpu:
            sys.exit("no TPU: this probe times the chip (--allow-cpu rehearses)")
        ks = jax.random.split(jax.random.key(0), 2)
        bins = jax.jit(lambda: jnp.where(
            jax.random.uniform(ks[0], (R, F)) < 0.02, B,
            jax.random.randint(ks[1], (R, F), 0, B)).astype(jnp.int16))()

        def operands(width):
            pos = jax.jit(lambda: jax.random.randint(
                ks[1], (R,), width - 1, 2 * width - 1, jnp.int32))()
            return bins, pos
    for width in (int(v) for v in args.widths.split(",")):
        ops = operands(width)
        got = {}
        for name, fn in forms(width).items():
            t0 = time.perf_counter()
            compiled = fn.lower(*ops).compile()
            line = (f"width {width:>3} {name:<8} compile "
                    f"{time.perf_counter() - t0:6.1f} s  temp "
                    f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:8.1f} MB")
            if not args.describe:
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    got[name] = jax.block_until_ready(compiled(*ops))
                    times.append(time.perf_counter() - t0)
                line += "  run " + " ".join(f"{t:.4f}" for t in times) + " s"
            print(line, flush=True)
        if "gather" in got and "dense" in got:
            same = bool(jnp.array_equal(got["gather"], got["dense"]))
            print(f"width {width:>3} dense equal to gather: {same}", flush=True)


if __name__ == "__main__":
    main()
