"""Histogram kernel parity: Pallas (interpret mode on CPU) vs XLA vs numpy
(the per-kernel test pattern of the reference, tests/cpp/tree/gpu_hist/)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.ops.histogram import build_histogram, node_sums
from xgboost_tpu.testing.reference import build_hist_np


def _mk(R=2048, F=6, B=16, n_nodes=4, node0=3, seed=0, with_missing=True):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B + (1 if with_missing else 0), size=(R, F)).astype(np.int16)
    gpair = rng.normal(size=(R, 2)).astype(np.float32)
    pos = rng.integers(node0 - 1, node0 + n_nodes + 1, size=R).astype(np.int32)
    return bins, gpair, pos


def _np_hist(bins, gpair, pos, node0, n_nodes, B):
    N = n_nodes
    F = bins.shape[1]
    out = np.zeros((N, F, B, 2), np.float64)
    for n in range(N):
        rows = np.nonzero(pos == node0 + n)[0]
        out[n] = build_hist_np(bins, gpair.astype(np.float64), rows, B)
    return out


def test_xla_histogram_matches_numpy():
    bins, gpair, pos, = _mk()
    node0, n_nodes, B = 3, 4, 16
    hist = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=node0, n_nodes=n_nodes, n_bin=B, chunk=512)
    )
    ref = _np_hist(bins, gpair, pos, node0, n_nodes, B)
    np.testing.assert_allclose(hist, ref, rtol=1e-4, atol=1e-4)


def test_pallas_histogram_matches_xla_interpret():
    from xgboost_tpu.ops.hist_pallas import build_histogram_pallas

    bins, gpair, pos = _mk(R=1024, F=7, B=16, seed=3)  # F=7 exercises padding
    node0, n_nodes, B = 3, 4, 16
    xla = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=node0, n_nodes=n_nodes, n_bin=B)
    )
    pallas = np.asarray(
        build_histogram_pallas(jnp.asarray(bins), jnp.asarray(gpair),
                               jnp.asarray(pos), node0=node0, n_nodes=n_nodes,
                               n_bin=B, interpret=True)
    )
    np.testing.assert_allclose(pallas, xla, rtol=1e-4, atol=1e-4)


def test_node_sums_matches_numpy():
    bins, gpair, pos = _mk()
    sums = np.asarray(node_sums(jnp.asarray(gpair), jnp.asarray(pos), node0=3, n_nodes=4))
    for n in range(4):
        ref = gpair[pos == 3 + n].sum(axis=0)
        np.testing.assert_allclose(sums[n], ref, rtol=1e-4, atol=1e-4)


def test_stride_selects_left_children():
    """stride=2 (subtraction trick) == every other slot of the full build."""
    bins, gpair, pos = _mk(R=2048, F=5, B=16, n_nodes=8, node0=7, seed=5)
    full = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=7, n_nodes=8, n_bin=16)
    )
    left = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=7, n_nodes=4, n_bin=16, stride=2)
    )
    np.testing.assert_allclose(left, full[0::2], rtol=1e-5, atol=1e-5)


def test_pallas_row_padding():
    """Rows not a multiple of the 512 tile are padded internally (the round-1
    R % 512 assert is gone)."""
    from xgboost_tpu.ops.hist_pallas import build_histogram_pallas

    bins, gpair, pos = _mk(R=700, F=3, B=8, n_nodes=2, node0=1, seed=7)
    xla = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=1, n_nodes=2, n_bin=8)
    )
    pallas = np.asarray(
        build_histogram_pallas(jnp.asarray(bins), jnp.asarray(gpair),
                               jnp.asarray(pos), node0=1, n_nodes=2, n_bin=8,
                               interpret=True)
    )
    np.testing.assert_allclose(pallas, xla, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sparsity", [0.0, 0.4])
def test_subtraction_trick_same_trees(sparsity):
    """Trees grown with the subtraction trick (right sibling = parent - left)
    choose the same splits as a direct rebuild of every node histogram
    (updater_gpu_hist.cu:309 SubtractHist)."""
    from xgboost_tpu.data.ellpack import build_ellpack
    from xgboost_tpu.data.quantile import sketch_dense
    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.tree.grow import HistTreeGrower

    rng = np.random.default_rng(11)
    R, F = 3000, 8
    X = rng.normal(size=(R, F)).astype(np.float32)
    if sparsity:
        X[rng.random((R, F)) < sparsity] = np.nan
    y = (np.nan_to_num(X[:, 0] * X[:, 1]) + np.nan_to_num(X[:, 2]) > 0)
    grad = (0.5 - y.astype(np.float32))
    gpair_np = np.stack([grad, np.full(R, 0.25, np.float32)], axis=1)

    cuts = sketch_dense(X, 16, use_device=False)
    ell = build_ellpack(X, cuts, row_align=64)
    gp = np.zeros((ell.n_padded, 2), np.float32)
    gp[:R] = gpair_np
    gp_j = jnp.asarray(gp)
    valid = jnp.arange(ell.n_padded) < R
    params = SplitParams(eta=0.3, gamma=0.0, min_child_weight=1.0,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)

    states = {}
    for sub in (True, False):
        g = HistTreeGrower(6, params, subtract=sub)
        states[sub] = HistTreeGrower.to_host(
            g.grow(ell.bins, gp_j, valid, ell.cuts_pad, ell.n_bins))
    np.testing.assert_array_equal(states[True].feat, states[False].feat)
    np.testing.assert_array_equal(states[True].sbin, states[False].sbin)
    np.testing.assert_array_equal(states[True].is_leaf, states[False].is_leaf)
    np.testing.assert_allclose(states[True].leaf_val, states[False].leaf_val,
                               rtol=1e-4, atol=1e-5)


def test_missing_sentinel_excluded():
    R, F, B = 512, 3, 8
    bins = np.full((R, F), B, np.int16)  # everything missing
    gpair = np.ones((R, 2), np.float32)
    pos = np.zeros(R, np.int32)
    hist = np.asarray(
        build_histogram(jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos),
                        node0=0, n_nodes=1, n_bin=B)
    )
    assert np.all(hist == 0.0)


def test_matmul_and_scatter_impls_agree(monkeypatch):
    """Both histogram implementations stay CI-covered on any backend via
    the XTB_HIST_IMPL override, and agree to f32 rounding (bitwise for the
    quantised int path) — including stride, traced node0, and the
    above-chunk scan branch."""
    import jax.numpy as jnp

    # the UNJITTED accumulators: the env override is read at trace time, so
    # a cached jit entry point would ignore a flip between two calls
    from xgboost_tpu.ops.histogram import _hist_accumulate
    from xgboost_tpu.ops.quantise import (hist_accumulate_q, local_rho,
                                          quantise_gpair)

    rng = np.random.default_rng(9)
    R, F, B, N = 3000, 5, 16, 4
    bins = jnp.asarray(rng.integers(0, B + 1, size=(R, F)).astype(np.int32))
    gp = jnp.asarray(rng.normal(size=(R, 2)).astype(np.float32))
    pos = jnp.asarray(rng.integers(-1, 2 * N, size=R).astype(np.int32))
    rho = local_rho(gp, jnp.ones(R, bool))
    gq = quantise_gpair(gp, rho)

    outs = {}
    for impl in ("matmul", "scatter"):
        monkeypatch.setenv("XTB_HIST_IMPL", impl)
        outs[impl] = (
            np.asarray(_hist_accumulate(bins, gp, pos, jnp.int32(3), N, B,
                                        512, 2)),
            np.asarray(hist_accumulate_q(bins, gq, pos, jnp.int32(1), N, B,
                                         chunk=512)),
        )
    np.testing.assert_allclose(outs["matmul"][0], outs["scatter"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(outs["matmul"][1], outs["scatter"][1])


def test_pallas_bench_shape_tiles_interpret():
    """Exercise the EXACT tile geometry choose_tiles picks for the bench
    shapes (HIGGS 28 columns / covertype 54, B=257 to cover the bin padding)
    in interpret mode, so that what compiles for the chip
    (tests/test_chip_compile.py) is a geometry the suite has validated
    numerically.  The feature group is the whole feature axis up to 32
    columns and 32 beyond (the only blocks Mosaic takes for every bin
    dtype).  Rows are reduced to 3 row tiles (tile geometry, padding and the
    cross-tile accumulate are row-count-invariant); the ragged final tile is
    included on purpose."""
    import numpy as np

    from xgboost_tpu.ops.hist_pallas import (build_histogram_pallas,
                                             choose_tiles)
    from xgboost_tpu.ops.histogram import build_histogram

    B = 257
    for F, n_nodes, stride, tile in ((28, 16, 2, 2048), (28, 32, 1, 2048),
                                     (54, 64, 2, 512)):
        T, FG = choose_tiles(F, B, n_nodes, 1)
        assert (T, FG) == (tile, min(F, 32)), (F, n_nodes, T, FG)
        rng = np.random.default_rng(F)
        R = 2 * T + 517  # two full tiles + ragged remainder
        bins = jnp.asarray(rng.integers(0, B + 1, size=(R, F)), jnp.int32)
        gpair = jnp.asarray(rng.normal(size=(R, 2)), jnp.float32)
        node0 = n_nodes - 1 if stride == 1 else 2 * n_nodes - 1
        pos = jnp.asarray(
            rng.integers(node0, node0 + stride * n_nodes, size=R), jnp.int32)
        got = build_histogram_pallas(
            bins, gpair, pos, node0=node0, n_nodes=n_nodes, n_bin=B,
            stride=stride, interpret=True, row_tile=T, feat_group=FG)
        want = build_histogram(bins, gpair, pos, node0=node0,
                               n_nodes=n_nodes, n_bin=B, stride=stride)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-4)


def test_pallas_quantised_bench_shape_tiles_interpret():
    """Same geometry pin for the quantised (int8 limb) kernel — bitwise."""
    import numpy as np

    from xgboost_tpu.ops.hist_pallas import (build_histogram_pallas_q,
                                             choose_tiles)
    from xgboost_tpu.ops.quantise import (hist_accumulate_q, local_rho,
                                          quantise_gpair)

    B, F, n_nodes = 257, 28, 16
    T, FG = choose_tiles(F, B, n_nodes, 1, out_ch=6)
    assert (T, FG) == (2048, 28)
    rng = np.random.default_rng(3)
    R = 2 * T + 301
    bins = jnp.asarray(rng.integers(0, B + 1, size=(R, F)), jnp.int32)
    gpair = jnp.asarray(rng.normal(size=(R, 2)), jnp.float32)
    rho = local_rho(gpair, jnp.ones(R, bool))
    gq = quantise_gpair(gpair, rho)
    node0 = 2 * n_nodes - 1
    pos = jnp.asarray(rng.integers(node0, node0 + 2 * n_nodes, size=R),
                      jnp.int32)
    got = build_histogram_pallas_q(
        bins, gq, pos, node0=node0, n_nodes=n_nodes, n_bin=B, stride=2,
        interpret=True, row_tile=T, feat_group=FG)
    want = hist_accumulate_q(bins, gq, pos, jnp.int32(node0), n_nodes, B,
                             stride=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _chunk_case(F, n_bin, dtype, R, n_nodes, node0, stride, seed):
    """A page with missing-sentinel bins (== n_bin) and rows above, below
    and between the built nodes; gpair in eighths, so float32 sums are exact
    in any order and the comparison below can ask for equality."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bin + 1, size=(R, F)).astype(dtype)
    gpair = (rng.integers(-64, 65, size=(R, 2)) / 8.0).astype(np.float32)
    pos = rng.integers(node0 - 2, node0 + stride * n_nodes + 2,
                       size=R).astype(np.int32)
    return jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos)


@pytest.mark.parametrize("page", ["int16", "uint8"])
@pytest.mark.parametrize("node0_kind", ["static", "traced"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("F", [1, 28, 136])
def test_feature_major_chunk_matches_scatter(monkeypatch, F, stride,
                                             node0_kind, page):
    """The chip's histogram (`_hist_chunk`, one-hot written feature-major as
    (F*B, T)) against the scatter driver at the cells' widths: 256 bins and
    the missing sentinel on an int16 page, 255 and the sentinel on a uint8
    one, chunk 0 outside the scan, a scanned chunk, and the ``rem`` tail."""
    from xgboost_tpu.ops.histogram import _hist_accumulate, scatter_hist_driver

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    n_bin, dtype = {"int16": (256, np.int16), "uint8": (255, np.uint8)}[page]
    chunk, N, node0 = 256, 3, 7
    bins, gpair, pos = _chunk_case(F, n_bin, dtype, 2 * chunk + 77, N, node0,
                                   stride, seed=F + stride)
    if node0_kind == "static":
        got = jax.jit(lambda b, g, p: _hist_accumulate(
            b, g, p, node0, N, n_bin, chunk, stride))(bins, gpair, pos)
    else:
        got = jax.jit(lambda b, g, p, n0: _hist_accumulate(
            b, g, p, n0, N, n_bin, chunk, stride))(bins, gpair, pos,
                                                   jnp.int32(node0))
    want = scatter_hist_driver(bins, gpair, pos, node0, N, n_bin, stride, 2,
                               jnp.float32)
    assert got.shape == (N, F, n_bin, 2)
    assert float(jnp.abs(want).sum()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_feature_major_chunk_under_vmap(monkeypatch):
    """`build_histogram_multi`: K lock-step trees over one page, the chunk
    body batched over gradient and positions and not over the bins."""
    from xgboost_tpu.ops.histogram import (build_histogram_multi,
                                           scatter_hist_driver)

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    K, F, B, N, node0 = 3, 5, 256, 2, 3
    R = 2 * 2048 + 100
    bins, gpair, pos = _chunk_case(F, B, np.int16, R, N, node0, 2, seed=1)
    rng = np.random.default_rng(2)
    gpair_rkc = jnp.stack([gpair * (k + 1) for k in range(K)], axis=1)
    pos_k = jnp.stack([jnp.asarray(rng.permutation(np.asarray(pos)))
                       for _ in range(K)])
    got = jax.jit(lambda *a: build_histogram_multi.__wrapped__(
        *a, n_nodes=N, n_bin=B, stride=2))(bins, gpair_rkc, pos_k,
                                            jnp.int32(node0))
    assert got.shape == (K, N, F, B, 2)
    for k in range(K):
        want = scatter_hist_driver(bins, gpair_rkc[:, k], pos_k[k], node0, N,
                                   B, 2, 2, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want))


def test_feature_major_chunk_under_shard_map(monkeypatch):
    """The scan's carry is seeded with chunk 0, so that under `shard_map` it
    enters with the varying type it leaves with (parallel/grower.py)."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from xgboost_tpu.ops.histogram import _hist_accumulate, scatter_hist_driver

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    F, B, N, node0, chunk = 4, 256, 2, 1, 256
    bins, gpair, pos = _chunk_case(F, B, np.int16, 3 * chunk + 50, N, node0,
                                   1, seed=4)

    def local(b, g, p):
        return jax.lax.psum(
            _hist_accumulate(b, g, p, node0, N, B, chunk, 1), "data")

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    got = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data", None), P("data", None),
                                    P("data")), out_specs=P()))(
        bins, gpair, pos)
    want = scatter_hist_driver(bins, gpair, pos, node0, N, B, 1, 2,
                               jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_LISTED = {
    # name: (rows at the built nodes, of R = 2 chunks and 77; None = every row)
    "empty": 0, "one-row": 1, "a-chunk": 256, "a-chunk-and-one": 257,
    "every-row": None, "one-node": 300}


@pytest.mark.parametrize("scan", ["list", "page"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("case", sorted(_LISTED))
def test_listed_histogram_matches_the_straight_one(monkeypatch, case, stride,
                                                   scan):
    """`build_histogram_listed` (the best-first pass's: a loop over the
    chunks of a row list, as long as the list; or, `scan` false, over the
    page's own chunks sliced inside the loop) against `_hist_accumulate` on
    the same `pos`, traced `node0`: an empty list, one row, a chunk to the
    row, a chunk and one, every row of a page that ends inside a chunk, the
    rows of one node.  Unit hessians: counts exact, gradient sums within
    1e-6 of the node's sum of |g| (other rows share a chunk, no more).  The
    list itself: grouped by node, rows in order inside a node, a row's node
    on its entry, rows at no built node (or between two, `stride=2`) left
    out; a list longer than `most` is counted, not written, and the page
    scanned."""
    from xgboost_tpu.ops.histogram import (RowList, _hist_accumulate,
                                           _row_bits, build_histogram_listed,
                                           row_list, row_list_fits,
                                           rows_scanned)

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    F, B, chunk, N, node0 = 5, 64, 256, 3, 7
    R = 2 * chunk + 77
    rng = np.random.default_rng(len(case) + stride)
    bins = jnp.asarray(rng.integers(0, B + 1, size=(R, F)).astype(np.int16))
    gpair = jnp.asarray(np.stack([rng.normal(size=R), np.ones(R)], 1)
                        .astype(np.float32))
    nodes = node0 + stride * np.arange(N)
    n = R if _LISTED[case] is None else _LISTED[case]
    pos = np.full(R, node0 - 1, np.int32)  # no built node, nor between two
    at = rng.permutation(R)[:n]
    pos[at] = nodes[0] if case == "one-node" else rng.choice(nodes, size=n)
    if stride == 2:  # rows between two built nodes are not listed
        between = rng.permutation(np.setdiff1d(np.arange(R), at))[:R // 8]
        pos[between] = node0 + 1
    # node0 traced, as the best-first pass hands it
    rows = jax.jit(lambda p, n0: row_list(
        p, n0, n_nodes=N, stride=stride,
        most=R if scan == "list" else n - 1))(jnp.asarray(pos),
                                              jnp.int32(node0))
    assert isinstance(rows, RowList) and row_list_fits(R, N)
    entries, count = rows.entries, rows.n
    assert int(count) == n and bool(rows.scan) == (scan == "list")
    # by node, then by row; a row's node rides above the row's bits
    if scan == "list":
        by_node = np.lexsort((at, pos[at]))
        np.testing.assert_array_equal(
            np.asarray(entries[:n]) & ((1 << _row_bits(R)) - 1), at[by_node])
        np.testing.assert_array_equal(
            node0 + stride * (np.asarray(entries[:n]) >> _row_bits(R)),
            pos[at[by_node]])
    pos = jnp.asarray(pos)
    assert int(rows_scanned(rows, R, chunk)) == (
        -(-n // chunk) * chunk if scan == "list" else R)
    got = jax.jit(lambda b, g, p, n0, r: build_histogram_listed.__wrapped__(
        b, g, p, n0, r, n_nodes=N, n_bin=B, chunk=chunk, stride=stride))(
            bins, gpair, pos, jnp.int32(node0), rows)
    want = jax.jit(lambda b, g, p, n0: _hist_accumulate(
        b, g, p, n0, N, B, chunk, stride))(bins, gpair, pos,
                                           jnp.int32(node0))
    assert got.shape == (N, F, B, 2)
    np.testing.assert_array_equal(np.asarray(got[..., 1]),
                                  np.asarray(want[..., 1]))
    assert float(got[..., 1].sum()) == float(
        (np.asarray(bins)[np.sort(at)] < B).sum())
    sum_abs = np.zeros(N)
    for i, node in enumerate(nodes):
        sum_abs[i] = np.abs(np.asarray(gpair)[np.asarray(pos) == node, 0]).sum()
    gap = np.abs(np.asarray(got[..., 0]) - np.asarray(want[..., 0])).max(
        axis=(1, 2))
    assert (gap <= 1e-6 * sum_abs).all(), (gap, sum_abs)


# ---- bin-width tiers: a one-hot as tall as a column's bins ----------------

def _tier_case(n_bins, n_bin, dtype, R, n_nodes, node0, stride, seed,
               one_row_a_bin=False):
    """A page whose column ``f`` holds bins below ``n_bins[f]`` and the
    sentinel; gradients on a grid (sums exact in float32 in any order).
    ``one_row_a_bin``: every row at one node and no bin of a column hit
    twice in a chunk's worth of rows."""
    rng = np.random.default_rng(seed)
    n_bins = np.asarray(n_bins)
    bins = np.stack([rng.integers(0, max(n, 1), size=R) for n in n_bins], 1)
    bins[rng.random(bins.shape) < 0.3] = n_bin
    bins[:, n_bins == 0] = n_bin
    pos = rng.integers(node0 - 2, node0 + stride * n_nodes + 2, size=R)
    if one_row_a_bin:
        bins = np.stack([np.where(np.arange(R) < n, np.arange(R), n_bin)
                         for n in n_bins], 1)
        pos = np.full(R, node0)
    gpair = (rng.integers(-64, 65, size=(R, 2)) / 8.0).astype(np.float32)
    return (jnp.asarray(bins.astype(dtype)), jnp.asarray(gpair),
            jnp.asarray(pos.astype(np.int32)))


def _n_bins_for(widths, n_bin, rng):
    """Column bin counts that ``bin_tiers`` sorts into ``widths``, shuffled."""
    lows = {32: 1, 64: 33, 128: 65}
    out = [rng.integers(lows.get(w, 129), w + 1, size=n) for w, n in widths]
    return rng.permutation(np.concatenate(out))


SIGNATURES = {
    "one": ((256, 40),),
    "32+256": ((32, 16), (256, 24)),
    "all-four": ((32, 32), (64, 16), (128, 16), (256, 21)),
    "uint8": ((32, 16), (64, 16), (255, 9)),
}


@pytest.mark.parametrize("form", ["static", "traced", "listed"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("signature", sorted(SIGNATURES))
def test_tiered_histogram_is_the_single_tier_one(monkeypatch, signature,
                                                 stride, form):
    """`level_histogram` with the page's tiers against the one without, on
    the chip's path: the same (N, F, B, C) sums in column order, exactly on
    gradients whose sums are exact; a column that holds only the sentinel
    rides in the narrowest tier; int16 and uint8 pages; chunk 0, a scanned
    chunk and the tail; the best-first pass's listed scan."""
    from xgboost_tpu.ops import histogram as H

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    widths = SIGNATURES[signature]
    n_bin = widths[-1][0]
    dtype = np.uint8 if signature == "uint8" else np.int16
    rng = np.random.default_rng(len(signature) + stride)
    n_bins = _n_bins_for(widths, n_bin, rng)
    n_bins[int(np.argmin(n_bins))] = 0  # a column with no value at all
    tiers = H.bin_tiers(n_bins, n_bin)
    if len(widths) == 1:
        assert tiers is None
        tiers = H.BinTiers(widths, jnp.arange(len(n_bins), dtype=jnp.int32))
    assert tiers.widths == widths
    chunk, N, node0 = 256, 3, 7
    R = 2 * chunk + 77
    bins, gpair, pos = _tier_case(n_bins, n_bin, dtype, R, N, node0, stride,
                                  seed=stride)
    rows = None
    if form == "listed":
        rows = H.row_list(pos, node0, n_nodes=N, stride=stride, most=R)
    node = node0 if form == "static" else jnp.int32(node0)

    def build(tiers):
        if form == "listed":
            return H.build_histogram_listed(
                bins, gpair, pos, node, rows, n_nodes=N, n_bin=n_bin,
                chunk=chunk, stride=stride, tiers=tiers)
        return jax.jit(lambda t: H._hist_accumulate(
            bins, gpair, pos, node, N, n_bin, chunk, stride, t))(tiers)

    got, want = build(tiers), build(None)
    assert got.shape == (N, len(n_bins), n_bin, 2)
    assert float(jnp.abs(want).sum()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and through the one way in, as the level body calls it
    via = H.level_histogram(bins, gpair, pos, node, n_nodes=N, n_bin=n_bin,
                            stride=stride, rows=rows, tiers=tiers)
    np.testing.assert_allclose(np.asarray(via), np.asarray(want), atol=1e-4)


def test_tiered_histogram_one_row_a_bin_is_exact(monkeypatch):
    """Where a chunk holds one row a bin nothing is summed inside it, so
    any gradients come back to the bit, tiers or none."""
    from xgboost_tpu.ops import histogram as H

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    rng = np.random.default_rng(5)
    n_bins = _n_bins_for(SIGNATURES["all-four"], 256, rng)
    tiers = H.bin_tiers(n_bins, 256)
    bins, _, pos = _tier_case(n_bins, 256, np.int16, 256, 1, 0, 1, seed=6,
                              one_row_a_bin=True)
    gpair = jnp.asarray(rng.normal(size=(256, 2)).astype(np.float32))
    got, want = (H.build_histogram(bins, gpair, pos, node0=0, n_nodes=1,
                                   n_bin=256, tiers=t) for t in (tiers, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    f = int(np.argmax(n_bins))
    np.testing.assert_array_equal(np.asarray(got[0, f, :n_bins[f]]),
                                  np.asarray(gpair[:n_bins[f]]))


@pytest.mark.parametrize("case", ["bosch", "counts", "continuous", "few",
                                  "narrow-page"])
def test_bin_tiers_signature_rule(case):
    """Counts are whole sublane tiles but for the widest tier's, every
    column sits in a tier that holds its bins, a tier with no column is not
    listed, and one tier is no tiers."""
    from xgboost_tpu.ops.histogram import bin_tiers, onehot_rows

    rng = np.random.default_rng(7)
    n_bin = 256
    if case == "bosch":  # 968 columns: 483, 137, 174, 174 by the bins needed
        n_bins = _n_bins_for(((32, 483), (64, 137), (128, 174), (256, 174)),
                             n_bin, rng)
        want = ((32, 480), (64, 128), (128, 176), (256, 184))
    elif case == "counts":  # 136 columns: 25, 5, 5, 101: no tier of 64
        n_bins = _n_bins_for(((32, 25), (64, 5), (128, 5), (256, 101)),
                             n_bin, rng)
        want = ((32, 16), (128, 16), (256, 104))
    elif case == "continuous":
        n_bins, want = np.full(28, 256), None
    elif case == "few":  # fewer than a tile of narrow columns
        n_bins, want = np.r_[np.full(15, 4), np.full(13, 256)], None
    else:  # a page no wider than the narrowest tier
        n_bin, n_bins, want = 32, rng.integers(1, 33, size=64), None
    tiers = bin_tiers(n_bins, n_bin)
    if want is None:
        assert tiers is None
        assert onehot_rows(tiers, n_bin, len(n_bins)) == n_bin * len(n_bins)
        return
    assert tiers.widths == want
    assert onehot_rows(tiers, n_bin, len(n_bins)) == sum(w * n for w, n in want)
    order = np.asarray(tiers.order)
    assert sorted(order) == list(range(len(n_bins)))
    lo = 0
    for w, n in tiers.widths:
        assert n > 0 and (n % 16 == 0 or w == n_bin)
        assert (n_bins[order[lo:lo + n]] <= w).all()
        lo += n


def test_bin_tiers_signature_is_steady_near_a_boundary():
    """Two sketches of one data set differ in a few columns' bins near a
    tier's edge (5 of 968 at 120,000 rows): the rounded counts agree, the
    columns' order need not."""
    from xgboost_tpu.ops.histogram import bin_tiers

    rng = np.random.default_rng(8)
    a = _n_bins_for(((32, 483), (64, 137), (128, 174), (256, 174)), 256, rng)
    b = a.copy()
    edge = np.flatnonzero((a > 28) & (a <= 32))[:3]
    b[edge] = 33                       # three columns need one bin more
    b[np.flatnonzero(a == 129)[:2]] = 128  # two a bin fewer
    ta, tb = bin_tiers(a, 256), bin_tiers(b, 256)
    assert len(edge) == 3 and (a != b).sum() >= 3
    assert ta.widths == tb.widths
    assert not np.array_equal(np.asarray(ta.order), np.asarray(tb.order))


# ---- the one-pass kernel: three exact bfloat16 terms against a bfloat16
# ---- one-hot (ops/hist_pallas.py), interpreted here ------------------------

_SPLIT_CASES = {
    "random": lambda rng: rng.normal(size=4096),
    "negative": lambda rng: -np.abs(rng.normal(size=4096)) * 7.3,
    "tiny": lambda rng: rng.normal(size=4096) * 1e-25,
    "huge": lambda rng: rng.normal(size=4096) * 1e37,
    "mixed": lambda rng: np.concatenate([
        [0.0, -0.0, 1.0, -1.0, 3.0e38, -3.0e38, 2.0 ** -100, 1 / 3],
        np.exp(rng.uniform(-60, 80, size=4096)) * rng.choice([-1, 1], 4096)]),
}


@pytest.mark.parametrize("case", sorted(_SPLIT_CASES))
def test_split3_is_exact(case):
    """``hi + mid + lo == g`` bit for bit, each term a bfloat16, for every
    float32 from 2**-102 to bfloat16's largest."""
    from xgboost_tpu.ops.hist_pallas import split3

    g = _SPLIT_CASES[case](np.random.default_rng(len(case))).astype(
        np.float32)
    terms = jax.jit(split3)(jnp.asarray(g))
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    hi, mid, lo = (np.asarray(t.astype(jnp.float32)) for t in terms)
    np.testing.assert_array_equal((hi + mid) + lo, g)
    assert np.all(np.abs(mid) <= np.abs(hi) * 2.0 ** -8 + 1e-45)
    assert np.all(np.abs(lo) <= np.abs(hi) * 2.0 ** -16 + 1e-45)


def test_split3_drops_only_what_float32_cannot_hold_as_a_third_term():
    """Under 2**-102 the third term falls under bfloat16's normal range and
    is flushed: what is lost is under 1.2e-38 an element, never more."""
    from xgboost_tpu.ops.hist_pallas import split3

    g = (np.random.default_rng(1).normal(size=4096) * 1e-33).astype(
        np.float32)
    hi, mid, lo = (np.asarray(t.astype(jnp.float32))
                   for t in jax.jit(split3)(jnp.asarray(g)))
    assert np.max(np.abs(((hi + mid) + lo) - g)) < 1.2e-38


_ONEPASS = {
    # name: (F, n_bin, dtype, n_nodes, node0, stride)
    "root": (28, 256, np.int16, 1, 0, 1),
    "16-nodes": (28, 256, np.int16, 16, 31, 2),
    "uint8": (28, 255, np.uint8, 16, 31, 2),
    "wide-page": (54, 256, np.int16, 4, 7, 2),
    "all-sentinel": (5, 64, np.int16, 2, 1, 1),
}


@pytest.mark.parametrize("node0_kind", ["static", "traced"])
@pytest.mark.parametrize("case", sorted(_ONEPASS))
def test_onepass_histogram_is_the_float32_sum(monkeypatch, case, node0_kind):
    """The one-pass kernel against a float64 sum of the same rows, within
    float32's rounding of a bin's magnitudes, and against the XLA form:
    root, 16 built nodes at stride 2, ``node0`` a constant or a scalar the
    kernel reads from SMEM, uint8 and int16 pages, the sentinel, two row
    tiles and a ragged third, a feature group and a ragged second."""
    from xgboost_tpu.ops.hist_pallas import onepass_histogram
    from xgboost_tpu.ops.histogram import _hist_accumulate

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    F, n_bin, dtype, N, node0, stride = _ONEPASS[case]
    T = 256
    R = 2 * T + 77
    rng = np.random.default_rng(F + N)
    bins = rng.integers(0, n_bin + 1, size=(R, F)).astype(dtype)
    if case == "all-sentinel":
        bins[:] = n_bin
    gpair = (rng.normal(size=(R, 2))
             * np.exp(rng.uniform(-8, 8, size=(R, 1)))).astype(np.float32)
    pos = rng.integers(node0 - 1, node0 + stride * N + 1,
                       size=R).astype(np.int32)
    node = node0 if node0_kind == "static" else jnp.int32(node0)
    got = np.asarray(jax.jit(lambda bt, g, p, n0: onepass_histogram(
        bt, g, p, n0, n_nodes=N, n_bin=n_bin, stride=stride, interpret=True,
        row_tile=T))(jnp.asarray(bins.T), jnp.asarray(gpair),
                     jnp.asarray(pos), node))
    assert got.shape == (N, F, n_bin, 2) and got.dtype == np.float32
    want = np.zeros((N, F, n_bin, 2))
    mags = np.zeros((N, F, n_bin, 2))
    for n in range(N):
        rows = np.nonzero(pos == node0 + stride * n)[0]
        for f in range(F):
            ok = rows[bins[rows, f] < n_bin]
            np.add.at(want[n, f], bins[ok, f], gpair[ok].astype(np.float64))
            np.add.at(mags[n, f], bins[ok, f], np.abs(gpair[ok]))
    assert (mags.sum() > 0) == (case != "all-sentinel")
    assert np.all(np.abs(got - want) <= 4 * 2.0 ** -24 * mags + 1e-30)
    xla = np.asarray(jax.jit(lambda b, g, p: _hist_accumulate(
        b, g, p, node0, N, n_bin, T, stride))(
            jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(pos)))
    assert np.all(np.abs(got - xla) <= 8 * 2.0 ** -24 * mags + 1e-30)


@pytest.mark.parametrize("signature", ["32+256", "all-four", "uint8"])
def test_onepass_histogram_of_a_page_with_tiers(monkeypatch, signature):
    """Through ``level_histogram``, told that the programs are a chip's: a
    kernel call a tier over ``transposed_page``'s arrays, each at its
    tier's height, comes back as the (N, F, B, C) histogram in column order
    that the XLA form gives; exact on gradients whose sums are exact."""
    from xgboost_tpu.ops import histogram as H

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    widths = SIGNATURES[signature]
    n_bin = widths[-1][0]
    dtype = np.uint8 if signature == "uint8" else np.int16
    rng = np.random.default_rng(len(signature))
    n_bins = _n_bins_for(widths, n_bin, rng)
    n_bins[int(np.argmin(n_bins))] = 0  # a column with no value at all
    tiers = H.bin_tiers(n_bins, n_bin)
    assert tiers.widths == widths
    N, node0, stride = 3, 7, 2
    bins, gpair, pos = _tier_case(n_bins, n_bin, dtype, 2 * 256 + 77, N,
                                  node0, stride, seed=3)
    want = H.level_histogram(bins, gpair, pos, jnp.int32(node0), n_nodes=N,
                             n_bin=n_bin, stride=stride, tiers=tiers)
    pages = H.transposed_page(bins, tiers)
    assert [p.shape for p in pages] == [(n, bins.shape[0]) for _, n in widths]
    monkeypatch.setattr(H, "_on_tpu", lambda: True)
    got = jax.jit(lambda b, g, p, n0, t, bt: H.level_histogram(
        b, g, p, n0, n_nodes=N, n_bin=n_bin, stride=stride, tiers=t,
        bins_t=bt))(bins, gpair, pos, jnp.int32(node0), tiers, pages)
    assert got.shape == want.shape and float(jnp.abs(want).sum()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_RULE = {
    # name: (n_nodes, extra, on a chip?, host impl, the form)
    "root-6-rows": (1, {}, True, "matmul", "onepass"),
    "32-slots-96-rows": (16, {}, True, "matmul", "onepass"),
    "21-nodes-126-rows": (21, {}, True, "matmul", "onepass"),
    "22-nodes-132-rows": (22, {}, True, "matmul", "xla"),
    "bestfirst-192-rows": (32, {}, True, "matmul", "xla"),
    "128-slots-384-rows": (64, {}, True, "matmul", "xla"),
    "int8-limbs": (16, {"quantised": True}, True, "matmul", "xla"),
    "row-list": (16, {"listed": True}, True, "matmul", "xla"),
    "mesh": (16, {"sharded": True}, True, "matmul", "xla"),
    "four-channels": (16, {"channels": 4}, True, "matmul", "xla"),
    "cpu-matmul": (1, {}, False, "matmul", "xla"),
    "row-pass-scatter": (1, {}, True, "scatter", "xla"),
}


@pytest.mark.parametrize("case", sorted(_RULE))
def test_hist_form_rule(monkeypatch, case):
    """``hist_form``: the one-pass kernel where the gradient operand's
    three terms fit one 128-wide tile, on a chip's one-hot matmul over the
    whole page with float32 sums; the XLA form everywhere else.  And
    ``level_histogram`` follows it: the kernel is traced exactly where the
    rule says ``onepass`` and the transposed page was handed in."""
    from xgboost_tpu.ops import histogram as H

    n_nodes, extra, on_tpu, impl, form = _RULE[case]
    monkeypatch.setenv("XTB_HIST_IMPL", impl)
    monkeypatch.setattr(H, "_on_tpu", lambda: on_tpu)
    assert H.hist_form(n_nodes, **extra) == form
    if set(extra) - {"sharded"}:
        return
    from xgboost_tpu.ops import hist_pallas

    calls = []
    kernel = hist_pallas.onepass_histogram
    monkeypatch.setattr(hist_pallas, "onepass_histogram",
                        lambda *a, **kw: calls.append(kw) or kernel(*a, **kw))
    R, F, B = 512, 4, 32
    args = (jnp.zeros((R, F), jnp.int16), jnp.zeros((R, 2), jnp.float32),
            jnp.zeros((R,), jnp.int32), jnp.int32(0))
    for handed, expect in ((True, form == "onepass"), (False, False)):
        del calls[:]
        out = jax.eval_shape(lambda b, g, p, n0, bt: H.level_histogram(
            b, g, p, n0, n_nodes=n_nodes, n_bin=B, bins_t=bt, **extra),
            *args, (jnp.zeros((F, R), jnp.int16),) if handed else None)
        assert out.shape == (n_nodes, F, B, 2)
        assert bool(calls) == expect


def test_grower_hands_the_transposed_page_where_the_rule_says(monkeypatch):
    """``HistTreeGrower.grow(resident=True)`` told that its programs are a
    chip's: the root and the 32-slot levels say ``hist_form=onepass`` on
    their span and read one kept copy of the page, the round counts them,
    and the tree is the XLA form's; without ``resident`` every level says
    ``xla`` and no copy is made."""
    from xgboost_tpu.data.ellpack import build_ellpack
    from xgboost_tpu.data.quantile import sketch_dense
    from xgboost_tpu.ops import histogram as H
    from xgboost_tpu.ops.split import SplitParams
    from xgboost_tpu.telemetry import spans
    from xgboost_tpu.tree.grow import HistTreeGrower

    monkeypatch.setenv("XTB_HIST_IMPL", "matmul")
    rng = np.random.default_rng(3)
    R, F = 1500, 6
    X = rng.normal(size=(R, F)).astype(np.float32)
    X[rng.random((R, F)) < 0.2] = np.nan
    y = np.nan_to_num(X[:, 0] * X[:, 1]) + np.nan_to_num(X[:, 2]) > 0
    ell = build_ellpack(X, sketch_dense(X, 32, use_device=False),
                        row_align=64)
    gp = np.zeros((ell.n_padded, 2), np.float32)
    gp[:R] = np.stack([0.5 - y, np.full(R, 0.25)], 1)
    valid = jnp.arange(ell.n_padded) < R
    params = SplitParams(eta=0.3, gamma=0.0, min_child_weight=1.0,
                         lambda_=1.0, alpha=0.0, max_delta_step=0.0)
    trees, forms, growers = {}, {}, {}
    for name, on_tpu, resident in (("xla", True, False),
                                   ("onepass", True, True),
                                   ("cpu", False, True)):
        monkeypatch.setattr(H, "_on_tpu", lambda on_tpu=on_tpu: on_tpu)
        jax.clear_caches()
        g = growers[name] = HistTreeGrower(3, params, padded_levels=True)
        for _ in range(2):  # the second tree reads the first one's copy
            trees[name] = HistTreeGrower.to_host(g.grow(
                ell.bins, jnp.asarray(gp), valid, ell.cuts_pad, ell.n_bins,
                resident=resident))
        forms[name] = [r["hist_form"] for r in spans.recent(
            "grow.build_hist+eval_split")[-4:]]
    assert forms["onepass"] == ["onepass"] * 3 + ["none"]
    assert forms["xla"] == forms["cpu"] == ["xla"] * 3 + ["none"]
    assert growers["xla"]._page_t is None and growers["cpu"]._page_t is None
    kept = growers["onepass"]._page_t
    assert kept[0] is ell.bins and kept[2][0].shape == ell.bins.shape[::-1]
    for name in ("onepass", "cpu"):
        np.testing.assert_array_equal(trees[name].feat, trees["xla"].feat)
        np.testing.assert_array_equal(trees[name].sbin, trees["xla"].sbin)
        np.testing.assert_allclose(trees[name].leaf_val,
                                   trees["xla"].leaf_val, rtol=1e-5,
                                   atol=1e-6)
