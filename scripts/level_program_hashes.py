"""sha1 of the lowered text of the level programs, to tell before the chip
whether an edit moves them: equal text is an equal key in the persistent
compile cache, and a moved key costs a HIGGS cell two cold compiles of 5 min
(PERF.md §5).  Run it on the parent and on the change and diff the output:

    python scripts/level_program_hashes.py [--root CHECKOUT] [--mesh | --v5e]

default  what HistTreeGrower.grow really dispatches, each program hashed at
         its first call: the cells' widths and depths, both width rules, both
         number formats, 8,192 rows, lowered for the CPU under the device's
         branches (XTB_HIST_IMPL=matmul, XTB_NO_NATIVE_SPLIT=1).
--mesh   the same for ShardedHistTreeGrower on four forced host devices.
--v5e    root, shared interior (one a width) and leaf program of each cell
         at its real shape, lowered for a described v5e (nothing compiles,
         nothing runs): the text the chip's cache key is made of, the levels
         that ``ops/histogram.py:hist_form`` gives the one-pass kernel
         handed the transposed page, as the grower hands it, at the widths
         the checkout's grower dispatches on a chip over a resident page.

In every mode the best-first pass of the cell higgs-leafwise-255.train
(tree/bestfirst.py ``level_step_bestfirst``) comes last, where the checkout
has one.

The one-pass kernel's Mosaic body would carry the file and line of every
frame that traced it; they are lowered as unknown locations here, so that a
line moved in a source file, or another checkout's path, does not read as a
moved program (the chip's key does move with them: a kernel program
compiles cold in seconds).
"""
import argparse
import hashlib
import inspect
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--mesh", action="store_true")
ap.add_argument("--v5e", action="store_true")
args = ap.parse_args()
os.environ.update(JAX_PLATFORMS="cpu", XTB_HIST_IMPL="matmul",
                  XTB_NO_NATIVE_SPLIT="1", TPU_LOG_DIR="disabled")
if args.mesh:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.abspath(args.root))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax._src.interpreters import mlir  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402

# no source locations in a Mosaic body (the module's docstring)
mlir._traceback_to_location = lambda ctx, tb: ir.Location.unknown()

from xgboost_tpu.ops.split import SplitParams  # noqa: E402
from xgboost_tpu.tree import grow  # noqa: E402

assert os.path.abspath(grow.__file__).startswith(os.path.abspath(args.root))
B = 256
PARAMS = SplitParams(eta=0.1, gamma=0.0, min_child_weight=1.0, lambda_=1.0,
                     alpha=0.0, max_delta_step=0.0)


def cat_mask(program, arg) -> tuple:
    """``(arg,)`` where ``program`` still takes the traced categorical mask
    between the set matrix and the rest (a checkout from before the
    categorical columns became static), else ``()``.  It can go once no
    checkout that old is hashed."""
    fn = getattr(program, "__wrapped__", program)
    return (arg,) if "cat_mask" in inspect.signature(fn).parameters else ()


def show(label, lowered):
    text = lowered.as_text()
    print(f"  {label:<44} {text.split('module @', 1)[1].split(' ', 1)[0]:<22} "
          f"{len(text.splitlines()):>5} lines  "
          f"{hashlib.sha1(text.encode()).hexdigest()[:12]}", flush=True)


class Recorder:
    """A jitted program that hashes itself at the first call of a kind."""

    def __init__(self, fn, label, seen):
        self.fn, self.label, self.seen = fn, label, seen

    def __call__(self, *a, **kw):
        kind = " ".join(f"{k}={kw[k]}" for k in ("depth", "width") if k in kw)
        if (self.label, kind) not in self.seen:
            self.seen.add((self.label, kind))
            show(f"{self.label} {kind}", self.fn.lower(*a, **kw))
        return self.fn(*a, **kw)


def data(F, R=8192):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, B, size=(R, F)).astype(np.int16)),
            jnp.asarray(rng.normal(size=(R, 2)).astype(np.float32)),
            jnp.ones(R, bool),
            jnp.asarray(np.sort(rng.normal(size=(F, B)), 1).astype(np.float32)),
            jnp.full(F, B, jnp.int32))


def dispatched(F, depth, shared, quantised):
    seen = set()
    if not args.mesh:
        grow.level_step = Recorder(LEVEL_STEP, "level_step", seen)
        grow.level_step_padded = Recorder(LEVEL_STEP_PADDED,
                                          "level_step_padded", seen)
        g = grow.HistTreeGrower(depth, PARAMS, padded_levels=shared,
                                quantised=quantised)
        return jax.block_until_ready(g.grow(*data(F)))
    from jax.sharding import Mesh

    from xgboost_tpu.parallel.grower import ShardedHistTreeGrower
    from xgboost_tpu.parallel.mesh import DATA_AXIS, shard_rows

    grow.default_padded_levels = lambda max_depth: shared
    mesh = Mesh(np.asarray(jax.devices()[:4]), (DATA_AXIS,))
    g = ShardedHistTreeGrower(depth, PARAMS, mesh, quantised=quantised)
    bins, gpair, valid, cuts, nb = data(F)
    g._build(F, B)
    g._init_fn = Recorder(g._init_fn, "init", seen)

    class Recorded(dict):
        """Interior programs, each hashed at its first call, whether
        ``_build`` makes them all or the loop one at a time."""

        def __setitem__(self, w, fn):
            super().__setitem__(w, Recorder(fn, f"interior width={w}", seen))

    interior, g._interior_fns = g._interior_fns, Recorded()
    for w, fn in interior.items():
        g._interior_fns[w] = fn
    for d in g._level_fns:
        g._level_fns[d] = Recorder(g._level_fns[d], f"level[{d}]", seen)
    bins, gpair, valid = shard_rows(mesh, bins, gpair, valid)
    jax.block_until_ready(g.grow(bins, gpair, valid, cuts, nb))


def for_v5e(F, depth, rows):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    state = jax.eval_shape(
        lambda g, v: grow.init_tree_state(
            g, v, max_nodes=grow.max_nodes_for_depth(depth), n_bin=B),
        jax.ShapeDtypeStruct((rows, 2), jnp.float32),
        jax.ShapeDtypeStruct((rows,), bool))
    head = (type(state)(*(shape(s.shape, s.dtype) for s in state)),
            shape((rows, F), jnp.int16), shape((rows, 2), jnp.float32),
            shape((F, B), jnp.float32), shape((F,), jnp.int32),
            shape((1, F), bool), shape((1, F), bool)) + cat_mask(
                grow.level_step, shape((F,), bool))
    common = dict(params=PARAMS, axis_name=None, lossguide=False,
                  has_cat=False, quantised=False)

    from xgboost_tpu.ops import hist_pallas, histogram

    onepass = hasattr(histogram, "hist_form")  # PR 37: the one-pass kernel
    if onepass:
        # the backend here is the CPU: tell the rule the programs are a chip's
        histogram._on_tpu = lambda: True
        hist_pallas._resolve_interpret = bool  # None: compiled, as on a chip

    def page_t(built):
        """The transposed page, where the checkout's grower would hand it to
        a level of ``built`` nodes."""
        return ({"bins_t": (shape((F, rows), jnp.int16),)}
                if onepass and histogram.hist_form(built) == "onepass" else {})

    grower = grow.HistTreeGrower(depth, PARAMS, padded_levels=True)
    if hasattr(grower, "_width"):  # PR 40: the ladder follows the kernel
        widths = {grower._width(d, True, 2, ((B, F),))
                  for d in range(1, depth)}
    else:
        widths = {grow.level_width(d, depth) for d in range(1, depth)}

    show("root", grow.level_step.lower(
        *head, None, None, depth=0, last_level=False, subtract=False,
        **common, **page_t(1)))
    # node0 as HistTreeGrower.grow passes it: a Python int, weakly typed
    for W in sorted(widths):
        show(f"shared interior width={W}", grow.level_step_padded.lower(
            *head, shape((W, F, B, 2), jnp.float32), 1, None, width=W,
            subtract=True, **common, **page_t(W // 2)))
    show(f"leaf level depth={depth}", grow.level_step.lower(
        *head, None, None, depth=depth, last_level=True, subtract=False,
        **common))


def bestfirst_pass(F, rows, leaves, sharding=None):
    """The one program of a best-first tree, as BestFirstGrower.grow calls
    it with no column sampling and no constraint."""
    from xgboost_tpu.tree import bestfirst

    if not hasattr(bestfirst, "level_step_bestfirst"):
        print("  (this checkout has no level_step_bestfirst)")
        return
    params = PARAMS._replace(min_child_weight=100.0)
    g = bestfirst.BestFirstGrower(0, params, max_leaves=leaves)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    state = jax.eval_shape(
        lambda pos, root: bestfirst._init_state(
            pos, root, S=g._grow_slots, F=F, B=B, n_sets=1),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32))
    show(f"best-first pass pairs={g.pairs}", bestfirst.level_step_bestfirst.lower(
        type(state)(*(shape(x.shape, x.dtype) for x in state)),
        shape((rows, F), jnp.int16), shape((rows, 2), jnp.float32),
        shape((F,), jnp.int32), shape((1, F), bool), shape((1, 2, F), bool),
        shape((1, F), bool),
        *cat_mask(bestfirst.level_step_bestfirst, shape((F,), bool)),
        pairs=g.pairs,
        max_leaves=leaves, max_depth=0, gamma_eps=1e-6, params=params,
        has_cat=False, monotone=False,
        # one chip's matmul scans a list of rows where they are few (PR 33)
        **({"list_rows": int(bestfirst._LIST_SHARE * rows)}
           if hasattr(bestfirst, "_LIST_SHARE") else {})))


LEVEL_STEP, LEVEL_STEP_PADDED = grow.level_step, grow.level_step_padded
if args.v5e:
    # the cells of BENCHMARK.json: columns, depth, rows as the page pads them
    for cell, F, depth, rows in (("higgs-d6.train", 28, 6, 10_500_096),
                                 ("higgs-d8.train", 28, 8, 10_500_096),
                                 ("mslr-web30k-ndcg.train", 136, 6, 2_271_232)):
        print(f"{cell}: {rows} x {F}, depth {depth}, for a described v5e")
        for_v5e(F, depth, rows)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    print("higgs-leafwise-255.train: 10500096 x 28, 255 leaves, for a "
          "described v5e")
    bestfirst_pass(28, 10_500_096, 255, SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2").devices[0]))
else:
    cases = [(28, 6, True, False), (28, 8, True, False), (136, 6, True, False),
             (28, 6, False, False), (28, 6, True, True), (28, 4, False, True),
             (28, 1, True, False), (28, 2, True, False)]
    for F, depth, shared, quantised in (cases[:1] + cases[3:6] if args.mesh
                                        else cases):
        print(f"F={F} depth={depth} "
              f"{'shared width' if shared else 'a program a depth'}"
              f"{', int8 limbs' if quantised else ''}")
        dispatched(F, depth, shared, quantised)
    if not args.mesh:
        print("F=28 best-first, 255 leaves")
        bestfirst_pass(28, 8192, 255)
