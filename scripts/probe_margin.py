"""What the margin update costs on the chip in its two forms
(``tree/grow.py``): the row-sized gather of each row's leaf value against
the dense select over the tree's slots, at the widths that set
``_MARGIN_DENSE_SLOTS`` (PERF.md §5).

    python scripts/probe_margin.py [--rows 10500096,40000512] [--slots 127,511,2047,8191]   # on a TPU host
    python scripts/probe_margin.py --describe          # compile only, for a described v5e
    JAX_PLATFORMS=cpu python scripts/probe_margin.py --rows 65536 --allow-cpu   # rehearse

Every row sits on one of the ``S`` slots or is padded (``pos`` -1, one row
in a hundred); the leaf values hold -0.0, NaN and the infinities.

gather   ``_margin_delta_gather``: ``leaf_val[pos]``, one index a row.
dense    ``_margin_delta_dense``: the bits selected over the ``S`` slots.

Times are host clock around a drained call, the least of ``--reps``; the
dense form's output is checked equal to the gather's as int32 bits.
"""
import argparse
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--rows", default="10500096,40000512")
ap.add_argument("--slots", default="127,511,2047,8191")
ap.add_argument("--reps", type=int, default=5)
ap.add_argument("--describe", action="store_true")
ap.add_argument("--allow-cpu", action="store_true")
args = ap.parse_args()
if args.describe:
    os.environ.update(JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from xgboost_tpu.tree import grow  # noqa: E402

FORMS = {"gather": jax.jit(grow._margin_delta_gather),
         "dense": jax.jit(grow._margin_delta_dense)}


def leaves(S: int):
    v = jax.random.normal(jax.random.key(S), (S,), jnp.float32)
    return v.at[:4].set(jnp.asarray([-0.0, jnp.nan, jnp.inf, -jnp.inf]))


def positions(R: int, S: int):
    k = jax.random.split(jax.random.key(R + S), 2)
    return jax.jit(lambda: jnp.where(
        jax.random.uniform(k[0], (R,)) < 0.01, -1,
        jax.random.randint(k[1], (R,), 0, S, jnp.int32)))()


def main():
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        operands = lambda R, S: (  # noqa: E731
            jax.ShapeDtypeStruct((R,), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((S,), jnp.float32, sharding=chip))
    else:
        platform = jax.devices()[0].platform
        print("device", platform, jax.devices()[0].device_kind, flush=True)
        if platform != "tpu" and not args.allow_cpu:
            sys.exit("no TPU: this probe times the chip (--allow-cpu rehearses)")
        operands = lambda R, S: (positions(R, S), leaves(S))  # noqa: E731
    for R in (int(v) for v in args.rows.split(",")):
        for S in (int(v) for v in args.slots.split(",")):
            ops = operands(R, S)
            got = {}
            for name, fn in FORMS.items():
                t0 = time.perf_counter()
                compiled = fn.lower(*ops).compile()
                line = (f"rows {R:>9} slots {S:>5} {name:<6} compile "
                        f"{time.perf_counter() - t0:5.1f} s  temp "
                        f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:7.1f} MB")
                if not args.describe:
                    times = []
                    for _ in range(args.reps):
                        t0 = time.perf_counter()
                        got[name] = jax.block_until_ready(compiled(*ops))
                        times.append(time.perf_counter() - t0)
                    line += (f"  least {min(times) * 1e3:8.3f} ms  ("
                             + " ".join(f"{t * 1e3:.3f}" for t in times) + ")")
                print(line, flush=True)
            if got:
                same = bool(jnp.array_equal(
                    lax.bitcast_convert_type(got["gather"], jnp.int32),
                    lax.bitcast_convert_type(got["dense"], jnp.int32)))
                print(f"rows {R:>9} slots {S:>5} dense bitwise equal to "
                      f"gather: {same}", flush=True)
            del ops, got


if __name__ == "__main__":
    main()
