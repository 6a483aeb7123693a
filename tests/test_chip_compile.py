"""The chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a v5e that is described
and not attached (section 2 of the on-chip-measurement guide).  These tests
hand it the programs of the main path at the real width — 28 columns, 256
bins, depth 6 — so that what Mosaic or XLA:TPU would refuse on the chip is
refused here, at no chip time.  Rows are cut to ROWS (the shape of record
has 2,000,000; compile time, not the verdict, depends on them); the width
never is.  A compile that passes is a compile: nothing runs, and nothing
here says anything about results or speed.

Interpret-mode CPU tests pinned every result these kernels give
(test_hist_kernels.py, test_quantised_hist.py) and still saw none of what
the first compile refused: a (2048, 16) block of a (R, 28) array.

The topology is described inside the module's fixture and nowhere else:
only one process at a time may load the TPU's library, every xdist worker
imports every test file, and so nothing here may touch ``topologies`` while
a module is imported.  The compiles are made in the test's own process, and
all of them live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

ROWS = 65_536  # of the 2,000,000 of the shape of record
F, B, DEPTH = 28, 256, 6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def device_paths(monkeypatch):
    """The branches a chip takes: here jax.default_backend() is the CPU, so
    the backend sniffs of ops/ would trace the host's FFI kernels."""
    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    monkeypatch.setenv("XTB_NO_NATIVE_SPLIT", "1")
    monkeypatch.setattr("xgboost_tpu.ops.histogram._on_tpu", lambda: True)
    monkeypatch.setattr("xgboost_tpu.ops.hist_pallas._resolve_interpret",
                        lambda interpret: bool(interpret))


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# root; and the widest level a depth-6 tree builds: the 16 left children of
# depth 5 (heap ids 31, 33, ...), the right ones coming by subtraction
LEVELS = {"root": dict(node0=0, n_nodes=1, stride=1),
          "depth5": dict(node0=31, n_nodes=16, stride=2)}


@pytest.mark.parametrize("level", sorted(LEVELS))
@pytest.mark.parametrize("bins_dtype", [jnp.uint8, jnp.int16],
                         ids=["uint8", "int16"])
@pytest.mark.parametrize("form", ["onepass", "int8limb"])
def test_fused_hist_kernel_compiles_for_v5e(one_chip, form, bins_dtype,
                                            level):
    """Both forms of the fused kernel (three bfloat16 terms with float32
    sums: what a round runs; int8 limbs with int32 sums: unwired), compiled
    and not interpreted, at the tiles choose_tiles picks.  uint8 is the page
    of max_bin <= 254; int16 is what the grower really holds at max_bin=256
    (257 symbols with the sentinel)."""
    from xgboost_tpu.ops.hist_pallas import (build_histogram_pallas,
                                             build_histogram_pallas_q)

    kernel, vals = {
        "onepass": (build_histogram_pallas,
                    _shape((ROWS, 2), jnp.float32, one_chip)),
        "int8limb": (build_histogram_pallas_q,
                     _shape((ROWS, 2, 3), jnp.int8, one_chip)),
    }[form]
    compiled = kernel.lower(
        _shape((ROWS, F), bins_dtype, one_chip), vals,
        _shape((ROWS,), jnp.int32, one_chip), n_bin=B, interpret=False,
        **LEVELS[level]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _level_args(sharding_rows, sharding_rep, depth=DEPTH, n_bin=B, F=F):
    """Shapes of one level step's operands at ROWS x 28 x 256, depth 6
    unless told another."""
    from xgboost_tpu.tree.grow import init_tree_state, max_nodes_for_depth

    state = jax.eval_shape(
        lambda g, v: init_tree_state(
            g, v, max_nodes=max_nodes_for_depth(depth), n_bin=n_bin),
        jax.ShapeDtypeStruct((ROWS, 2), jnp.float32),
        jax.ShapeDtypeStruct((ROWS,), bool))
    state = type(state)(*(
        _shape(s.shape, s.dtype,
               sharding_rows if name == "pos" else sharding_rep)
        for name, s in zip(state._fields, state)))
    return (state,
            _shape((ROWS, F), jnp.int16, sharding_rows),  # bins
            _shape((ROWS, 2), jnp.float32, sharding_rows),  # gpair
            _shape((F, n_bin), jnp.float32, sharding_rep),  # cuts_pad
            _shape((F,), jnp.int32, sharding_rep),        # n_bins
            _shape((1, F), bool, sharding_rep),           # feature_mask
            _shape((1, F), bool, sharding_rep))           # set_matrix


def _split_params():
    from xgboost_tpu.ops.split import SplitParams

    return SplitParams(eta=0.1, gamma=0.0, min_child_weight=1.0,
                       lambda_=1.0, alpha=0.0, max_delta_step=0.0)


def test_root_level_program_compiles_for_v5e(one_chip, device_paths):
    """``level_step`` at depth 0, as HistTreeGrower.grow calls it by
    default on a chip: XLA one-hot matmul histogram, XLA split scan."""
    from xgboost_tpu.tree.grow import level_step

    def root(*args):  # a fresh function, so a fresh trace under device_paths
        return level_step.__wrapped__(
            *args, None, None, depth=0, params=_split_params(),
            last_level=False, subtract=False)

    compiled = jax.jit(root).lower(*_level_args(one_chip, one_chip)).compile()
    text = compiled.as_text()
    assert "custom_call_target=\"xtb_" not in text  # no host FFI kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 31


@pytest.mark.parametrize("depth", [DEPTH, 8])
def test_padded_level_program_compiles_for_v5e(one_chip, device_paths, depth):
    """``level_step_padded``: the program the interior depths of a width
    share, 32 node slots wide, the 16 left ones built and the rest
    subtracted.  At depth 6 it is every interior level's; at depth 8, with
    the 511-slot state, levels 1-5's (``level_width``)."""
    from xgboost_tpu.tree.grow import level_step_padded, level_width

    W = level_width(1, depth)
    assert W == 32

    def interior(*args):
        return level_step_padded.__wrapped__(
            *args, width=W, params=_split_params(), subtract=True)

    args = _level_args(one_chip, one_chip, depth) + (
        _shape((W, F, B, 2), jnp.float32, one_chip),  # hist_prev
        _shape((), jnp.int32, one_chip))              # node0, traced
    compiled = jax.jit(interior).lower(*args).compile()
    assert "custom_call_target=\"xtb_" not in compiled.as_text()


@pytest.mark.parametrize("program", ["root", "width32", "width64",
                                     "width128", "width128-1024-bins"])
def test_level_programs_hold_the_onepass_kernel_where_the_rule_says(
        one_chip, device_paths, program):
    """The level programs as ``HistTreeGrower.grow(resident=True)`` runs
    them on a chip, each handed the transposed page: ``level_step`` at depth
    0 (6 operand rows) and ``level_step_padded`` at 32, 64 and 128 slots
    (96, 192 and 384 rows: depth 8's ladder) hold the Mosaic kernel and no
    one-hot convolution.  At 1,024 bins the kernel's block of sums at 384
    rows would outgrow VMEM, and the 128-slot level compiles in the XLA
    form.  The best-first pass (192 rows) is never handed a transposed
    page: ``test_bestfirst_pass_compiles_for_v5e`` holds it to its
    convolutions under the same fixture, and a mesh keeps the XLA form
    (``test_sharded_level_program_allreduces_on_four_chips``)."""
    from xgboost_tpu.tree.grow import level_step, level_step_padded

    page_t = (_shape((F, ROWS), jnp.int16, one_chip),)
    n_bin = 1024 if program.endswith("-1024-bins") else B
    if program == "root":
        def step(page_t, *args):  # a fresh function: a fresh trace
            return level_step.__wrapped__(
                *args, None, None, depth=0, params=_split_params(),
                last_level=False, subtract=False, bins_t=page_t)
        args = _level_args(one_chip, one_chip)
    else:
        W = int(program[5:].split("-")[0])

        def step(page_t, *args):
            return level_step_padded.__wrapped__(
                *args, width=W, params=_split_params(), subtract=True,
                bins_t=page_t)
        args = _level_args(one_chip, one_chip, 8, n_bin) + (
            _shape((W, F, n_bin, 2), jnp.float32, one_chip),
            _shape((), jnp.int32, one_chip))
    text = jax.jit(step).lower(page_t, *args).compile().as_text()
    assert ("tpu_custom_call" in text) == (n_bin == B)
    assert (" convolution(" in text) == (n_bin != B)
    assert "custom_call_target=\"xtb_" not in text


@pytest.mark.parametrize("program", ["root", "width32"])
def test_categorical_level_programs_compile_for_v5e(one_chip, device_paths,
                                                    program):
    """The airline cell's level programs: 8 columns, six of them categorical
    with up to 300 categories (past max_bin, so ``B`` is 300 and the page
    int16), the one-pass kernel over the transposed page, the categorical
    scan and the membership route.  No gather or scatter may have a
    row-sized output: the route brings each row its node's set by selects
    (``_membership_dense``), not by a gather of the (N, B) sets."""
    import re

    from xgboost_tpu.tree.grow import level_step, level_step_padded

    F_CAT, B_CAT, cats = 8, 300, (0, 1, 2, 4, 5, 6)
    page_t = (_shape((F_CAT, ROWS), jnp.int16, one_chip),)
    if program == "root":
        def step(page_t, *args):  # a fresh function: a fresh trace
            return level_step.__wrapped__(
                *args, None, None, depth=0, params=_split_params(),
                last_level=False, subtract=False, has_cat=cats,
                bins_t=page_t)
        args = _level_args(one_chip, one_chip, n_bin=B_CAT, F=F_CAT)
    else:
        def step(page_t, *args):
            return level_step_padded.__wrapped__(
                *args, width=32, params=_split_params(), subtract=True,
                has_cat=cats, bins_t=page_t)
        args = _level_args(one_chip, one_chip, n_bin=B_CAT, F=F_CAT) + (
            _shape((32, F_CAT, B_CAT, 2), jnp.float32, one_chip),
            _shape((), jnp.int32, one_chip))
    compiled = jax.jit(step).lower(page_t, *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "custom_call_target=\"xtb_" not in text
    moved = re.findall(r"= \w+\[(\d+)[\],][^\n]* (?:gather|scatter)\(", text)
    assert not moved or max(int(n) for n in moved) < ROWS, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 31


@pytest.mark.parametrize("slots", [127, 510, 511])
def test_margin_update_selects_without_a_gather_for_v5e(one_chip, device_paths,
                                                      slots):
    """The margin update at the cells' tree widths (depth 6, a leaf budget
    of 255, depth 8): the dense select, no gather with a row-sized output,
    and the (S, R) operand of its compare never in memory."""
    import re

    from xgboost_tpu.tree.grow import leaf_margin_delta

    compiled = jax.jit(lambda p, v: leaf_margin_delta.__wrapped__(p, v)).lower(
        _shape((ROWS,), jnp.int32, one_chip),
        _shape((slots,), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    moved = re.findall(r"= \w+\[(\d+)[\],][^\n]* gather\(", text)
    assert not moved or max(int(n) for n in moved) < ROWS, moved
    assert compiled.memory_analysis().temp_size_in_bytes < slots * ROWS


def test_bestfirst_pass_compiles_for_v5e(one_chip, device_paths):
    """``level_step_bestfirst`` at 28 columns, 256 bins and the cell's budget
    of 255 leaves: 32 pairs a pass, the one-hot matmul for the 32 built
    children, the packed-table route, the replayed queue, and on one chip
    the list of the built children's rows with the scan over its chunks.
    Nothing in it may gather or scatter a row-sized array (PERF.md, PR 27:
    0.05-0.1 s each at 10.5M rows: the listed scan gathers a chunk's 2,048
    rows at a time), the list is one sort, and no host kernel may be traced
    into it."""
    import re

    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.tree import bestfirst

    params = SplitParams(eta=0.1, gamma=0.0, min_child_weight=100.0,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)
    grower = bestfirst.BestFirstGrower(0, params, max_leaves=255)
    assert grower.pairs == 32
    state = jax.eval_shape(
        lambda pos, root: bestfirst._init_state(
            pos, root, S=grower._grow_slots, F=F, B=B, n_sets=1),
        jax.ShapeDtypeStruct((ROWS,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32))
    state = type(state)(*(_shape(s.shape, s.dtype, one_chip) for s in state))

    def one_pass(*args):  # a fresh function: a fresh trace under device_paths
        return bestfirst.level_step_bestfirst.__wrapped__(
            *args, pairs=grower.pairs, max_leaves=255, max_depth=0,
            gamma_eps=1e-6, params=params, has_cat=False, monotone=False,
            list_rows=int(bestfirst._LIST_SHARE * ROWS))

    compiled = jax.jit(one_pass).lower(
        state, _shape((ROWS, F), jnp.int16, one_chip),
        _shape((ROWS, 2), jnp.float32, one_chip),
        _shape((F,), jnp.int32, one_chip), _shape((1, F), bool, one_chip),
        _shape((1, 2, F), bool, one_chip),
        _shape((1, F), bool, one_chip)).compile()
    text = compiled.as_text()
    assert "custom_call_target=\"xtb_" not in text
    assert "tpu_custom_call" not in text  # 32 pairs: 192 operand rows
    moved = re.findall(r"= \w+\[(\d+)[\],][^\n]* (?:gather|scatter)\(", text)
    assert moved and max(int(n) for n in moved) <= max(grower._grow_slots,
                                                       2048), moved
    assert len(re.findall(r"= \w+\[%d\][^\n]* sort\(" % ROWS, text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 31


def test_sharded_level_program_allreduces_on_four_chips(topo, device_paths,
                                                        monkeypatch):
    """What ``n_devices=4`` runs: the padded level step under shard_map on a
    mesh of the described chip's four devices.  The histogram has to cross
    the chips, so the compiled text holds an all-reduce."""
    from xgboost_tpu.parallel.grower import ShardedHistTreeGrower
    from xgboost_tpu.parallel.mesh import DATA_AXIS

    # the platform rule for sharing one padded program asks the default
    # backend, which is the CPU here: hold it to what a chip gets
    monkeypatch.setattr("xgboost_tpu.tree.grow.default_padded_levels",
                        lambda max_depth: True)
    mesh = Mesh(np.asarray(topo.devices[:4]), (DATA_AXIS,))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())
    grower = ShardedHistTreeGrower(DEPTH, _split_params(), mesh)
    grower._build(F, B)
    W = 1 << (DEPTH - 1)
    args = _level_args(rows, rep) + (
        _shape((W, F, B, 2), jnp.float32, rep), _shape((), jnp.int32, rep))
    compiled = grower._interior_fn(W).lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
    assert "tpu_custom_call" not in compiled.as_text()  # a mesh: the XLA form


def test_ranking_gradient_compiles_for_v5e(one_chip):
    """The top-k LambdaMART gradient at the width of the ranking cell: groups
    1,251 slots wide, 32 pairs a document, 2,080 groups in 20 blocks of 104
    (the cell has 18,919 in 182; a block is the same program).  Inside the
    block loop: two sorts and no gather; after it, the two row-sized gathers
    back to row order."""
    import re

    from xgboost_tpu.objective.ranking import (_lambda_gradients_topk,
                                               make_topk_layout)

    sizes = np.full(2080, 120)
    sizes[::100] = 1251
    sizes[1::100] = 1
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    layout = make_topk_layout(ptr, np.zeros(ptr[-1], np.float32), 32)
    assert layout.gain.shape == (20, 104, 1251)
    rows = -(-int(ptr[-1]) // 2048) * 2048
    compiled = _lambda_gradients_topk.lower(
        _shape((rows,), jnp.float32, one_chip),
        jax.tree.map(lambda a: _shape(a.shape, a.dtype, one_chip), layout),
        k=32, ndcg_weight=True, score_norm=True, group_norm=True).compile()
    text = compiled.as_text()
    assert len(re.findall(r" sort\(", text)) == 2
    gathers = re.findall(r" = (\S+) gather\(", text)
    assert len(gathers) == 2 and all(g.startswith(f"f32[{ptr[-1]}]")
                                     for g in gathers)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


@pytest.mark.parametrize("columns", [136, 968])
@pytest.mark.parametrize("form", ["scan", "listed"])
def test_hist_onehot_is_built_inside_the_matmul_for_v5e(one_chip, device_paths,
                                                        form, columns):
    """The structure PR 29 rests on, at the ranking cell's width (136
    columns, 16 built nodes) and at the widest cell's (968 columns, where a
    stored one-hot would be 507 MB a chunk): the one-hot is written feature-major, so the
    chip's compiler makes the broadcast, the iota and the ``==`` producers
    inside the convolution's fusion.  Row-major it cut them out as arrays of
    their own, `s32[2048,136,256]` (285 MB a chunk: the program's whole temp
    size then) and `pred[2048,34816]`, written and read back a chunk a level.
    ``listed``: the best-first pass's two loops (`build_histogram_listed`:
    gathered chunks of a row list, or the page's chunks sliced in the body)
    hold the same form, and neither copies its accumulator a chunk."""
    import re

    from xgboost_tpu.ops.histogram import (RowList, build_histogram_at,
                                           build_histogram_listed)

    kernel = {"scan": build_histogram_at,
              "listed": build_histogram_listed}[form]

    def build(*args):  # a fresh function: a fresh trace under device_paths
        return kernel.__wrapped__(*args, n_nodes=16, n_bin=B, stride=2)

    F_RANK, T = columns, 2048
    rows = () if form == "scan" else (RowList(
        entries=_shape((ROWS,), jnp.int32, one_chip),
        n=_shape((), jnp.int32, one_chip), scan=_shape((), bool, one_chip)),)
    compiled = jax.jit(build).lower(
        _shape((ROWS, F_RANK), jnp.int16, one_chip),
        _shape((ROWS, 2), jnp.float32, one_chip),
        _shape((ROWS,), jnp.int32, one_chip),
        _shape((), jnp.int32, one_chip), *rows).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < T * F_RANK * B
    onehot = re.compile(
        r" = \w+\[(%d,%d,%d|%d,%d|%d,%d,%d|%d,%d)[\],]" % (
            T, F_RANK, B, T, F_RANK * B, F_RANK, B, T, F_RANK * B, T))
    text = compiled.as_text()
    # chunk 0 and the scan's body; the list's loop and the page's
    assert len(re.findall(r" convolution\(", text)) == 2
    computation, stored, copied = "", [], []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[0]
        elif onehot.search(line) and "fused_computation" not in computation:
            stored.append(line.strip()[:120])
        elif ("region" in computation and re.search(
                r" = f32\[16,%d,%d,2\]\S* copy" % (F_RANK, B), line)):
            copied.append(line.strip()[:120])
    assert not stored, stored
    assert not copied, copied


# bosch-d8.train's signature (946,997 x 968: 483, 137, 174 and 174 columns
# by the bins they need, each tier's count moved down to whole tiles)
BOSCH_TIERS = ((32, 480), (64, 128), (128, 176), (256, 184))


@pytest.mark.parametrize("form", ["scan", "listed"])
def test_tiered_onehots_are_built_inside_their_matmuls_for_v5e(
        one_chip, device_paths, form):
    """Bin-width tiers at 968 columns hold PR 29's structure a tier: four
    matmuls a chunk (chunk 0 and the scan's body; the list's loop and the
    page's), each with its one-hot a producer inside its fusion, so that no
    ``pred[...]`` or ``s32[2048,F_w,w]`` array of a tier's size is stored;
    no accumulator of a tier is copied in a loop's body; and what moves the
    chunk's columns into tier order is one gather of the transposed chunk,
    int16 as the page is."""
    import re

    from xgboost_tpu.ops.histogram import (BinTiers, RowList,
                                           build_histogram_at,
                                           build_histogram_listed)

    kernel = {"scan": build_histogram_at,
              "listed": build_histogram_listed}[form]

    def build(*args, tiers):  # a fresh function: a fresh trace
        return kernel.__wrapped__(*args, n_nodes=16, n_bin=B, stride=2,
                                  tiers=tiers)

    columns, T = sum(n for _, n in BOSCH_TIERS), 2048
    assert columns == 968
    rows = () if form == "scan" else (RowList(
        entries=_shape((ROWS,), jnp.int32, one_chip),
        n=_shape((), jnp.int32, one_chip), scan=_shape((), bool, one_chip)),)
    compiled = jax.jit(build).lower(
        _shape((ROWS, columns), jnp.int16, one_chip),
        _shape((ROWS, 2), jnp.float32, one_chip),
        _shape((ROWS,), jnp.int32, one_chip),
        _shape((), jnp.int32, one_chip), *rows,
        tiers=BinTiers(BOSCH_TIERS, _shape((columns,), jnp.int32, one_chip))
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r" convolution\(", text)) == 2 * len(BOSCH_TIERS)
    sizes = "|".join(
        "%d,%d,%d|%d,%d|%d,%d,%d|%d,%d" % (T, n, w, T, n * w, n, w, T,
                                           n * w, T)
        for w, n in BOSCH_TIERS)
    onehot = re.compile(r" = \w+\[(%s)[\],]" % sizes)
    accumulator = re.compile(r" = f32\[16,(%s),2\]\S* copy" % "|".join(
        "%d,%d" % (n, w) for w, n in BOSCH_TIERS))
    computation, stored, copied = "", [], []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[0]
        elif onehot.search(line) and "fused_computation" not in computation:
            stored.append(line.strip()[:120])
        elif "region" in computation and accumulator.search(line):
            copied.append(line.strip()[:120])
    assert not stored, stored
    assert not copied, copied
    moved = re.findall(r" = (\w+)\[%d,%d\]\S* gather\(" % (columns, T), text)
    assert moved == ["s16"] * 2, moved


@pytest.mark.parametrize("program", ["level_step", "level_step_padded",
                                     "level_step_bestfirst"])
def test_one_tier_lowers_to_the_program_without_tiers(one_chip, device_paths,
                                                      program):
    """A page whose every column needs all ``B`` bins (the three HIGGS
    cells) has the signature of one tier, and its level programs are the
    parent's text: the cache's key does not move and the cells keep their
    executables (PERF.md: a reordered text costs each program its 5 min)."""
    from xgboost_tpu.ops.histogram import BinTiers
    from xgboost_tpu.tree import bestfirst, grow

    one = BinTiers(((B, F),), None)
    if program == "level_step_bestfirst":
        params = _split_params()._replace(min_child_weight=100.0)
        grower = bestfirst.BestFirstGrower(0, params, max_leaves=255)
        state = jax.eval_shape(
            lambda pos, root: bestfirst._init_state(
                pos, root, S=grower._grow_slots, F=F, B=B, n_sets=1),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.float32))
        args = (type(state)(*(_shape(s.shape, s.dtype, one_chip)
                              for s in state)),
                _shape((ROWS, F), jnp.int16, one_chip),
                _shape((ROWS, 2), jnp.float32, one_chip),
                _shape((F,), jnp.int32, one_chip),
                _shape((1, F), bool, one_chip),
                _shape((1, 2, F), bool, one_chip),
                _shape((1, F), bool, one_chip))
        static = dict(pairs=grower.pairs, max_leaves=255, max_depth=0,
                      gamma_eps=1e-6, params=params, has_cat=False,
                      monotone=False,
                      list_rows=int(bestfirst._LIST_SHARE * ROWS))
        step = bestfirst.level_step_bestfirst
    elif program == "level_step":
        args = _level_args(one_chip, one_chip) + (None, None)
        static = dict(depth=0, params=_split_params(), last_level=False,
                      subtract=False)
        step = grow.level_step
    else:
        args = _level_args(one_chip, one_chip) + (
            _shape((32, F, B, 2), jnp.float32, one_chip), 1, None)
        static = dict(width=32, params=_split_params(), subtract=True)
        step = grow.level_step_padded
    texts = [step.lower(*args, **static, **extra).as_text()
             for extra in ({}, {"tiers": None}, {"tiers": one})]
    assert texts[0] == texts[1] == texts[2]
    assert "dot_general" in texts[0]
