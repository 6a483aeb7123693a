"""Gradient histogram construction — the hot kernel of hist tree growing.

TPU-native re-design of the reference's histogram build
(src/tree/gpu_hist/histogram.cu:37-120 shared-memory atomic kernels;
CPU src/tree/hist/histogram.h:44).  The CUDA design — atomic adds of quantised
(grad,hess) into per-node bins — does not map to TPU (no fast global atomics).
Instead we reformulate as a **masked one-hot matmul** that runs on the MXU:

    hist[n, f, b, c] = sum_r  onehot(bins[r,f], b) * (pos[r] == node(n)) * gpair[r, c]

i.e. ``A @ G`` with ``A = onehot(bins)`` of shape (F*B, rows) and
``G[r, n*2+c] = gpair[r,c] * nodemask[r,n]`` of shape (rows, 2N).  No row
sorting, no scatter, no atomics; per-row node membership lives in a ``pos``
array updated elementwise each level (the analogue of RowPartitioner positions,
src/tree/gpu_hist/row_partitioner.cuh:255, without the physical partition).

What runs on the chip is ``build_histogram`` (the root) and
``build_histogram_at`` (every later level): the matmul in float32 at
``HIGHEST`` under ``lax.scan`` over 2,048-row chunks, compiled by XLA.  The
one-hot is written **feature-major**, ``(F, B, T)`` from the transposed chunk
and contracted as ``(F*B, T) @ (T, 2N)``, because XLA:TPU lays a chunk out
with its rows in the lanes (``s16[n,2048,F]{1,2,0}``): rows then stay the
minor dimension from the page to the matmul's operand, ``bins_c.T`` is a
bitcast, and the broadcast, the iota and the ``==`` become producers inside
the convolution's fusion.  Row-major, ``(T, F, B)`` reshaped to ``(T, F*B)``
and transposed, merges F and B under a minor dimension that has already moved,
and the compiler then stores an ``s32[2048,F,256]`` broadcast (285 MB at 136
columns, through HBM once a chunk a level) and a ``pred[2048,F*256]`` compare
as arrays of their own: 74% of a round at 136 columns, 35% at 28 (PERF.md §6,
PR 29).  tests/test_chip_compile.py holds the fused form in place.
``build_histogram_listed`` is the same chunk under a loop whose length is
known only on the device: over a list of rows, gathered 2,048 at a time, so
that the histogram of nodes that hold a few per cent of the page costs
their rows and not the page (the best-first pass, tree/bestfirst.py).

**Bin-width tiers.**  The matmul costs what its one-hot is tall (PERF.md §5:
linear in rows x ``F*B``), and a column whose sketch made 20 cuts fills 20
of its ``B = 256`` one-hot rows: the others are all-zero by construction,
multiplied in every chunk of every level, and thrown away by the split scan
(ops/split.py masks ``bin >= n_bins[f]``).  So a chunk's one-hot is built a
*tier* at a time: the columns that need at most 32 bins 32 rows tall, then
those within 64, 128 and ``B``, one dot a tier against the chunk's one
gradient operand, one accumulator a tier under the scan.  After the scan,
once a level, ``_untier`` pads each tier with zeros to ``B`` and puts the
columns back in their order: callers see the (N, F, B, C) histogram they
always saw, to float32's last bits.  ``bin_tiers`` reads the tiers off the
sketch's ragged cuts.  How many columns each tier holds (``BinTiers.widths``)
is static to the level programs, which columns (``BinTiers.order``) an
operand, so the counts are rounded: each tier keeps a multiple of 16 columns
(a packed int16 sublane tile of the transposed chunk, so a tier's slice of it
is aligned) and hands the rest, those with the most bins, to the next; the
few columns a sample's noise moves across a boundary then move no count, and
the seeds of one data set share their executables.  The chunk's columns are
gathered into tier order inside the scan and the page stays in column order
for every other reader (stored in tier order the level is 3% faster on the
chip: PERF.md §6, PR 35).  One tier is the program without tiers, text and
cache key.

Which of these a level gets is decided here and nowhere else:
``level_histogram`` is what the level body (tree/grow.py), the page step
(tree/stream.py) and the best-first pass (tree/bestfirst.py) call.  It picks
float32 or int8-limb sums by ``quantised``, the static or the traced entry
point by what ``node0`` is, the listed scan where it is handed a list of
rows (the best-first pass alone hands one), and the one-hot
matmul, the XLA scatter or the native row-pass kernel by ``_host_impl``: on
the CPU backend neither matmul runs by default.

**One pass a tile of the operand.**  The XLA form costs 2.05 ps an element
of the one-hot up to 64 output columns (three bfloat16 passes for ``HIGHEST``
over an operand built through float32) and runs at 89% of that peak at 128.
Where ``hist_form`` says ``onepass`` (a TPU's matmul, float32 sums, the
whole page on one chip, ``3 * C * n_nodes <= 384``: from the root's 6 operand
rows to a 128-slot level's 384) and the caller hands the transposed page
(``transposed_page``: the depth-wise grower keeps one), the level is the
fused kernel of ops/hist_pallas.py, a call a tier: the gradient pair split
into three exact bfloat16 terms, a bfloat16 one-hot built in VMEM and held
in the MXU while the operand streams past it a 128-row tile at a time, the
same float32 sums (PERF.md §6, PR 37 and PR 40).  The grower's ladder of
widths follows the rule (tree/grow.py ``level_width``); whatever is not
handed the transposed page keeps the XLA form.  The kernel's int8-limb form
is compiled and pinned by its tests and has no caller yet (ROADMAP D1).

Determinism: float32 accumulation in a fixed sequential chunk order — within
one topology, the role played by fixed-point gradient quantisation in the
reference (src/tree/gpu_hist/quantiser.cuh:52) is filled by the absence of
atomics.  For bitwise reproducibility ACROSS topologies (any chip/process
layout), ``deterministic_histogram=True`` switches to exact int8-limb
histograms with integer reductions — see ops/quantise.py.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# A float32 matmul at default precision runs on the MXU as one bfloat16 pass:
# the 0/1 one-hot operand survives that exactly, the gradient operand is
# rounded to 8 mantissa bits before it is summed.  Every histogram matmul is
# pinned, so that "float32 histogram" means float32 on the chip too.
_EXACT_F32 = lax.Precision.HIGHEST


def _onehot_feature_major(bins_c, n_bin: int, dtype):
    """(T, F) bins -> the (F*B, T) 0/1 operand of a chunk's matmul, float32 or
    int8 (ops/quantise.py).  Rows stay the minor dimension, as the chunk is
    laid out on the chip, so the compare is a producer inside the matmul's
    fusion and the one-hot is never stored (module docstring).  The missing
    sentinel (bin == n_bin) compares false everywhere."""
    T, F = bins_c.shape
    onehot = (bins_c.T.astype(jnp.int32)[:, None, :]
              == jnp.arange(n_bin, dtype=jnp.int32)[None, :, None])  # (F, B, T)
    return onehot.astype(dtype).reshape(F * n_bin, T)


# The heights a column's one-hot is built at, under the page's own B: the
# narrowest that holds the column's bins (module docstring: bin-width tiers).
_TIER_WIDTHS = (32, 64, 128)
# A tier's columns come in whole tiles of the transposed chunk: an int16
# chunk packs 16 columns to a sublane tile, so a tier that starts and ends on
# a multiple of 16 is sliced out of ``bins_c.T`` without a relayout.
_TIER_COLUMNS = 16


@functools.partial(jax.tree_util.register_dataclass, data_fields=["order"],
                   meta_fields=["widths"])
@dataclasses.dataclass(frozen=True)
class BinTiers:
    """A page's columns by the height their one-hot needs (``bin_tiers``).
    To a jitted program ``widths`` is static and ``order`` an operand: one
    program a signature, whichever columns fill it."""

    widths: tuple         # ((bins, columns), ...), narrowest first
    order: "jnp.ndarray"  # (F,) int32 - the columns, tier after tier


def bin_tiers(n_bins, n_bin: int) -> Optional[BinTiers]:
    """The tiers of a page whose column ``f`` holds bins ``[0, n_bins[f])``
    under the common width ``n_bin``; None where one tier of ``n_bin`` is all
    there is (every column needs it, or there are fewer than 16 that do
    not).  The columns sorted by their bins are cut where the narrowest
    width that holds them changes, each cut moved down to a multiple of
    ``_TIER_COLUMNS``: a tier keeps whole tiles and hands the columns over,
    those with the most bins, to the next; a tier left with none is not
    listed.  ``widths`` is a static argument of the level programs, so it
    should not follow the few columns that a sample's noise moves across a
    boundary: the rounding is what two seeds of one data set agree on."""
    import numpy as np

    n_bins = np.asarray(n_bins)
    by_bins = np.argsort(n_bins, kind="stable")
    heights = [w for w in _TIER_WIDTHS if w < n_bin] + [n_bin]
    ends = [int(np.sum(n_bins <= w)) // _TIER_COLUMNS * _TIER_COLUMNS
            for w in heights[:-1]] + [len(n_bins)]
    widths, order, lo = [], [], 0
    for w, hi in zip(heights, ends):
        if hi > lo:
            widths.append((w, hi - lo))
            order.append(np.sort(by_bins[lo:hi]))
            lo = hi
    if len(widths) <= 1:
        return None
    return BinTiers(tuple(widths),
                    jnp.asarray(np.concatenate(order), jnp.int32))


def tier_widths(tiers: Optional[BinTiers], n_bin: int, F: int):
    """((bins, columns), ...) of the tiers; of the one that no tiers are."""
    return ((n_bin, F),) if tiers is None else tiers.widths


def onehot_rows(tiers: Optional[BinTiers], n_bin: int, F: int) -> int:
    """Rows of the one-hot operand a chunk is multiplied as: ``F * n_bin``
    without tiers."""
    return sum(w * n for w, n in tier_widths(tiers, n_bin, F))


def _by_tier(parts, tiers: Optional[BinTiers]):
    """A chunk's or a level's sums as they are carried: one array a tier,
    the array itself where there are no tiers."""
    return parts[0] if tiers is None else tuple(parts)


def _hist_chunk(bins_c, gpair_c, pos_c, node0: int, n_nodes: int, n_bin: int,
                stride: int = 1, tiers: Optional[BinTiers] = None):
    """One row-chunk's contribution: (T,F) bins -> (N,F,B,C) partial
    histogram; under ``tiers`` one (N,F_w,w,C) a tier, columns in tier order
    (``_untier`` is their way back)."""
    T, F = bins_c.shape
    C = gpair_c.shape[1]
    widths = tier_widths(tiers, n_bin, F)
    if tiers is None:
        columns = [bins_c]
    else:
        # the chunk's columns brought into tier order, then each tier's
        # one-hot only as tall as its bins: a bin at or above ``w`` (the
        # sentinel too) compares false everywhere, and no column of a tier
        # has a row in one
        ordered = bins_c.T[tiers.order].T
        ends = list(itertools.accumulate(n for _, n in widths))
        columns = [ordered[:, hi - n:hi] for (_, n), hi in zip(widths, ends)]
    onehots = [_onehot_feature_major(cols, w, jnp.float32)
               for cols, (w, _) in zip(columns, widths)]
    nodemask = (
        pos_c[:, None] == (node0 + stride * jnp.arange(n_nodes, dtype=pos_c.dtype))
    ).astype(jnp.float32)  # (T, N)
    gm = (nodemask[:, :, None] * gpair_c[:, None, :]).reshape(T, n_nodes * C)
    return _by_tier([
        jnp.dot(
            onehot, gm, preferred_element_type=jnp.float32,
            precision=_EXACT_F32,
        )  # (F*B, N*C)
        .reshape(n, w, n_nodes, C).transpose(2, 0, 1, 3)
        for onehot, (w, n) in zip(onehots, widths)], tiers)


def _untier(acc, tiers: Optional[BinTiers], n_bin: int):
    """A level's sums by tier -> the (N, F, B, C) histogram in column order,
    each tier padded with the zeros its missing bins would have summed to:
    once a level, after the scan."""
    if tiers is None:
        return acc
    padded = [jnp.pad(a, ((0, 0), (0, 0), (0, n_bin - w), (0, 0)))
              for a, (w, _) in zip(acc, tiers.widths)]
    back = jnp.zeros_like(tiers.order).at[tiers.order].set(
        jnp.arange(tiers.order.shape[0], dtype=tiers.order.dtype))
    return jnp.concatenate(padded, axis=1)[:, back]


def _add(acc, part):
    """``acc + part``, a tier at a time where they come in tiers."""
    return jax.tree.map(jnp.add, acc, part)


@functools.partial(jax.jit,
                   static_argnames=("node0", "n_nodes", "n_bin", "chunk", "stride"))
def build_histogram(
    bins, gpair, pos, *, node0: int, n_nodes: int, n_bin: int, chunk: int = 2048,
    stride: int = 1, tiers: Optional[BinTiers] = None
):
    """hist (n_nodes, F, B, C) for nodes node0 + stride*[0, n_nodes).

    bins  : (R_pad, F) int   — local bin indices, sentinel == n_bin for missing
    gpair : (R_pad, C) f32   — C=2 (grad, hess); padded rows must be zero
    pos   : (R_pad,) int32   — per-row node id (-1 for padded rows)
    stride: 2 selects every other heap slot — the left-children of a level,
            for the subtraction trick (right sibling = parent - left).
    tiers : ``bin_tiers`` of the page: the same sums (to float32's last
            bits) over a one-hot as tall as each column's bins.
    """
    return _hist_accumulate(bins, gpair, pos, node0, n_nodes, n_bin, chunk,
                            stride, tiers)


def hist_impl_override():
    """Test hook: XTB_HIST_IMPL=matmul|scatter|native forces the
    implementation regardless of backend, so the TPU matmul path keeps CPU
    CI coverage (tests/test_hist_kernels.py) and vice versa."""
    import os

    v = os.environ.get("XTB_HIST_IMPL", "").lower()
    return v if v in ("matmul", "scatter", "native") else None


def _host_impl():
    """Implementation for the CPU backend: the native C++ row-pass kernel
    (native/xtb_kernels.h via an XLA FFI custom call, ~5-10x the XLA
    scatter's add rate) when the handler library is present, else the XLA
    scatter driver."""
    forced = hist_impl_override()
    if forced == "native":
        # the forced hook must still register the FFI targets (and is the
        # one place where failure should be loud, not a silent fallback)
        from ..utils import native

        if not native.load_ffi():
            raise RuntimeError(
                "XTB_HIST_IMPL=native but the FFI kernel library could not "
                "be built/loaded (see native/Makefile `make ffi`)")
        return "native"
    if forced is not None:
        return forced
    if jax.default_backend() != "cpu":
        return "matmul"
    from ..utils import native

    return "native" if native.ffi_usable() else "scatter"


def hist_is_row_pass() -> bool:
    """Whether a level's histogram is a pass over the rows that adds where
    a row's node matches (the native kernel, the XLA scatter) and not the
    dense one-hot matmul: what the route and the shared width follow."""
    return _host_impl() in ("scatter", "native")


def _native_hist(bins, gpair, pos, node0, n_nodes, n_bin, stride):
    """XLA FFI custom call into the native hist kernel (CPU backend only).

    node0 may be traced (the padded shared level program) — it rides as an
    operand.  Works under shard_map: the custom call fires per shard on that
    shard's rows, exactly the partial-histogram semantics the psum expects.

    The kernel is internally multi-threaded (feature-sharded ParallelFor,
    native/xtb_kernels.h) with bitwise-identical output for every nthread;
    ensure_pool() applies the process's thread-count default before the
    first dispatch."""
    import numpy as np

    from ..utils import native

    native.ensure_pool()
    R, F = bins.shape
    C = gpair.shape[1]
    if bins.dtype not in (jnp.uint8, jnp.uint16, jnp.int16, jnp.int32):
        bins = bins.astype(jnp.int32)
    call = jax.ffi.ffi_call(
        "xtb_hist",
        jax.ShapeDtypeStruct((n_nodes, F, n_bin, C), jnp.float32))
    return call(bins, gpair.astype(jnp.float32), pos.astype(jnp.int32),
                jnp.asarray(node0, jnp.int32).reshape(1),
                stride=np.int32(stride))


def scatter_hist_driver(bins, values, pos, node0, n_nodes, n_bin, stride,
                        out_cols, dtype, row_chunk: int = 1 << 18):
    """Shared CPU scatter-add scaffolding (flat index construction, stride
    and missing-sentinel masking, chunk-0-outside-the-scan carry rule) for
    the f32 and quantised-limb histograms: O(R*F) adds instead of the
    matmul's O(R*F*B) MACs (~150x faster on one core; XLA's CPU scatter is
    sequential, hence deterministic).  The TPU path keeps the one-hot
    matmul: on the MXU the matmul wins and scatter serializes (the round-1
    design decision this fallback deliberately inverts).

    values: (R, out_cols) already in the accumulator dtype.
    """
    R, F = bins.shape
    M = n_nodes * F * n_bin

    def chunk_add(flat, sl):
        b, g, p = sl
        local = p - node0
        if stride != 1:
            ok = (local >= 0) & (local % stride == 0) \
                & (local // stride < n_nodes)
            node = jnp.where(ok, local // stride, 0)
        else:
            ok = (local >= 0) & (local < n_nodes)
            node = jnp.where(ok, local, 0)
        idx = (node[:, None] * (F * n_bin)
               + jnp.arange(F, dtype=jnp.int32)[None, :] * n_bin
               + jnp.minimum(b.astype(jnp.int32), n_bin - 1))
        # missing sentinel (bin == n_bin) and out-of-level rows add zero
        w = (ok[:, None] & (b.astype(jnp.int32) < n_bin)).astype(dtype)
        vals = g[:, None, :] * w[:, :, None]          # (T, F, out_cols)
        return flat.at[idx.reshape(-1)].add(vals.reshape(-1, out_cols))

    flat = jnp.zeros((M, out_cols), dtype)
    if R <= row_chunk:
        flat = chunk_add(flat, (bins, values, pos))
    else:
        n_chunks = R // row_chunk
        rem = R - n_chunks * row_chunk
        # chunk 0 outside the scan: the carry must already have the
        # shard-varying type under shard_map (same rule as the matmul path)
        flat = chunk_add(flat, (bins[:row_chunk], values[:row_chunk],
                                pos[:row_chunk]))
        xs = (bins[row_chunk: n_chunks * row_chunk].reshape(
                  n_chunks - 1, row_chunk, F),
              values[row_chunk: n_chunks * row_chunk].reshape(
                  n_chunks - 1, row_chunk, out_cols),
              pos[row_chunk: n_chunks * row_chunk].reshape(
                  n_chunks - 1, row_chunk))
        flat, _ = lax.scan(lambda a, sl: (chunk_add(a, sl), None), flat, xs)
        if rem:
            flat = chunk_add(flat, (bins[-rem:], values[-rem:], pos[-rem:]))
    return flat.reshape(n_nodes, F, n_bin, out_cols)


def _hist_accumulate(bins, gpair, pos, node0, n_nodes, n_bin, chunk, stride,
                     tiers: Optional[BinTiers] = None):
    """Fixed-order chunked accumulation shared by the static- and
    traced-node0 entry points (node0 may be an int or a traced scalar).
    The row-pass kernels add a row where its bin is and build no one-hot:
    ``tiers`` are the matmul's alone."""
    impl = _host_impl()
    if impl == "native":
        return _native_hist(bins, gpair, pos, node0, n_nodes, n_bin, stride)
    if impl == "scatter":
        return scatter_hist_driver(bins, gpair, pos, node0, n_nodes, n_bin,
                                   stride, gpair.shape[1], jnp.float32)
    R, F = bins.shape
    C = gpair.shape[1]

    def part(b, g, p):
        return _hist_chunk(b, g, p, node0, n_nodes, n_bin, stride, tiers)

    if R <= chunk:
        return _untier(part(bins, gpair, pos), tiers, n_bin)
    n_chunks = R // chunk
    rem = R - n_chunks * chunk

    def body(acc, xs):
        return _add(acc, part(*xs)), None

    # seed the carry with chunk 0 (not zeros): under shard_map the chunk
    # contributions vary over the data axis, and a scan carry must enter
    # with the same varying type it leaves with
    acc0 = part(bins[:chunk], gpair[:chunk], pos[:chunk])
    xs = (
        bins[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk, F),
        gpair[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk, C),
        pos[chunk: n_chunks * chunk].reshape(n_chunks - 1, chunk),
    )
    acc, _ = lax.scan(body, acc0, xs)
    if rem:
        acc = _add(acc, part(bins[-rem:], gpair[-rem:], pos[-rem:]))
    return _untier(acc, tiers, n_bin)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bin", "chunk",
                                             "stride"))
def build_histogram_at(bins, gpair, pos, node0, *, n_nodes: int, n_bin: int,
                       chunk: int = 2048, stride: int = 1,
                       tiers: Optional[BinTiers] = None):
    """build_histogram with a TRACED starting node id.

    The best-first grower expands one node pair at a time with fresh ids,
    and the padded level step walks depths with one compiled program; a
    static node0 would recompile the kernel per expansion/depth, so here
    node0 is an operand (it only feeds the node-mask comparison, never a
    shape).
    """
    node0 = jnp.asarray(node0, jnp.int32)
    return _hist_accumulate(bins, gpair, pos, node0, n_nodes, n_bin, chunk,
                            stride, tiers)


class RowList(NamedTuple):
    """The rows a histogram is wanted of, where they may be few (``row_list``)."""

    entries: jnp.ndarray  # (R,) int32 - a row and its node, by node, then row
    n: jnp.ndarray        # () int32 - how many
    scan: jnp.ndarray     # () bool - scan the list; else the page, straight


def _row_bits(n_rows: int) -> int:
    """Bits of a list's entry that hold the row; the node's index among the
    nodes asked for rides above them."""
    return max(n_rows - 1, 1).bit_length()


def row_list_fits(n_rows: int, n_nodes: int) -> bool:
    """Whether a row and its node fit one int32 entry of a list (below the
    entry that no row holds): 67M rows at 32 nodes."""
    return _row_bits(n_rows) + max(n_nodes - 1, 1).bit_length() <= 30


def row_list(pos, node0, *, n_nodes: int, stride: int = 1,
             most: int) -> RowList:
    """The rows that ``pos`` (R,) places at the nodes ``node0 + stride*[0,
    n_nodes)``, to be scanned as a list where they are ``most`` at the most
    (else the page is, and the list is not written).  An entry is the node's
    index above the row's bits, so one sort of the entries (a row elsewhere
    sorts past every row listed) writes the list grouped by node, rows in
    order inside a node, and the scan reads a row's node off its entry: the
    slot's gather a listed row, 20-40 ns of 72-105, is not paid.  The sort
    stands in a loop of one trip, or of none where the list is empty or too
    long to be scanned: 10 ms at 10.5M rows, which seven of a tree's
    eighteen passes would pay for nothing.  PERF.md §6, PR 33, has what the
    other ways to write the list cost on the chip (a prefix sum + scatter:
    six times the sort)."""
    R = pos.shape[0]
    assert row_list_fits(R, n_nodes), (R, n_nodes)
    local = pos - node0
    at = local // stride
    wanted = (local >= 0) & (local % stride == 0) & (at < n_nodes)
    n = jnp.sum(wanted, dtype=jnp.int32)
    entry = jnp.where(
        wanted, (at << _row_bits(R)) | jnp.arange(R, dtype=jnp.int32),
        jnp.iinfo(jnp.int32).max)
    scan = n <= most
    entries = lax.fori_loop(
        0, (scan & (n > 0)).astype(jnp.int32),
        lambda _, e: lax.sort(e, is_stable=False), entry)
    return RowList(entries=entries, n=n, scan=scan)


def _scan_chunks(rows: RowList, n_rows: int, T: int):
    """(chunks of the list, chunks of the page) that a scan runs, ``T`` rows
    each: one of the two is nought."""
    return (jnp.where(rows.scan, -(-rows.n // T), 0),
            jnp.where(rows.scan, 0, -(-n_rows // T)))


def rows_scanned(rows: RowList, n_rows: int, chunk: int = 2048):
    """How many rows ``build_histogram_listed`` visits: whole chunks of the
    list, or the page."""
    T = min(chunk, n_rows)
    return jnp.where(rows.scan, _scan_chunks(rows, n_rows, T)[0] * T,
                     n_rows).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bin", "chunk",
                                             "stride"))
def build_histogram_listed(bins, gpair, pos, node0, rows: RowList, *,
                           n_nodes: int, n_bin: int, chunk: int = 2048,
                           stride: int = 1,
                           tiers: Optional[BinTiers] = None):
    """``build_histogram_at`` at the cost of the rows it is wanted of.

    Two loops whose trip counts are known only on the device, one of them
    with none: where ``rows.scan`` holds, ``ceil(rows.n / chunk)`` chunks of
    ``chunk`` listed rows each, gathered from the page with their gradient
    pair (an empty list: no chunk); else the page straight, its chunks
    sliced from it inside the loop's body, so that a pass over a short list
    pays nothing by the page's size.  ``rows`` is ``row_list`` of the same
    ``pos``, ``node0``, ``n_nodes`` and ``stride``.  The arithmetic is
    ``_hist_chunk``'s in both loops: only which rows share a chunk differs,
    and with it the last bits of a sum.  One-hot matmul only (the row-pass kernels of the CPU backend cost little
    a row as it is), and no ``lax.cond``: around the scan it made the
    accumulator be copied every chunk (PERF.md §6, PR 32)."""
    R, F = bins.shape
    T = min(chunk, R)
    bits = _row_bits(R)
    node0 = jnp.asarray(node0, jnp.int32)
    lane = jnp.arange(T, dtype=jnp.int32)
    in_list, in_page = _scan_chunks(rows, R, T)

    def window(i):
        """Start of chunk ``i`` (moved back where it would pass the end) and
        the places of it that chunk ``i - 1`` has not had."""
        start = jnp.minimum(i * T, R - T)
        return start, start + lane >= i * T

    def add(acc, b, g, p, ok):
        return _add(acc, _hist_chunk(b, g, jnp.where(ok, p, -1), node0,
                                     n_nodes, n_bin, stride, tiers))

    def from_page(i, acc):
        start, fresh = window(i)
        return add(acc, lax.dynamic_slice(bins, (start, 0), (T, F)),
                   lax.dynamic_slice(gpair, (start, 0), (T, gpair.shape[1])),
                   lax.dynamic_slice(pos, (start,), (T,)), fresh)

    def from_list(i, acc):
        start, fresh = window(i)
        ok = fresh & (start + lane < rows.n)
        entry = lax.dynamic_slice(rows.entries, (start,), (T,))
        at = jnp.where(ok, entry & ((1 << bits) - 1), 0)
        return add(acc, bins.at[at].get(mode="promise_in_bounds"),
                   gpair.at[at].get(mode="promise_in_bounds"),
                   node0 + stride * (entry >> bits), ok)

    acc = _by_tier([jnp.zeros((n_nodes, n, w, gpair.shape[1]), jnp.float32)
                    for w, n in tier_widths(tiers, n_bin, F)], tiers)
    acc = lax.fori_loop(0, in_page, from_page, acc)
    return _untier(lax.fori_loop(0, in_list, from_list, acc), tiers, n_bin)


# The widest gradient operand the one-pass kernel takes: three bfloat16 terms
# of two channels of 64 built nodes, a 128-slot level (768 never measured).
_ONEPASS_ROWS = 384


def _on_tpu() -> bool:
    """Whether programs traced now are the chip's (the fused kernel compiles
    for a TPU and nothing else runs it in a round)."""
    return jax.default_backend() == "tpu"


def hist_form(n_nodes: int, *, channels: int = 2, quantised: bool = False,
              listed: bool = False, sharded: bool = False,
              widths: tuple = ()) -> str:
    """``"onepass"`` or ``"xla"``: how a level that builds ``n_nodes`` nodes
    gets its histogram, from what the code sees and nothing else.  The fused
    one-pass kernel (ops/hist_pallas.py) where the chip's one-hot matmul
    would run, the sums are float32, the page is scanned whole on one chip,
    the operand is at most three 128-row tiles (``3 * channels * n_nodes
    <= 384``: the root's 6 rows to a 128-slot level's 384; the grower's
    ladder of widths follows it), and the kernel's working set fits VMEM
    for each of ``widths``, the ((bins, columns), ...) of its calls
    (``tier_widths``; 384 rows of 1,024 bins do not).  Wider levels, the
    int8-limb sums, a list of rows, a mesh, processes and the CPU's
    row-pass kernels keep the XLA form; each further tile is one more pass
    over the one-hot (PERF.md §6)."""
    fits = 3 * channels * n_nodes <= _ONEPASS_ROWS
    if (fits and _on_tpu() and _host_impl() == "matmul" and not quantised
            and not listed and not sharded
            and _kernel_fits(n_nodes, channels, widths)):
        return "onepass"
    return "xla"


def _kernel_fits(n_nodes: int, channels: int, widths: tuple) -> bool:
    from .hist_pallas import tiles_fit

    return all(tiles_fit(n, w, n_nodes, out_ch=3 * channels)
               for w, n in widths)


@jax.jit
def transposed_page(bins, tiers: Optional[BinTiers] = None):
    """The page as the one-pass kernel reads it: one ``(F_w, R)`` array a
    tier, rows in the lanes, a tier's columns in tier order (one array where
    there are no tiers, or one).  Made once a page by whoever keeps the page
    (tree/grow.py), not a level: at 10.5M x 28 the transpose costs 2.7 ms,
    a twentieth of the root's level (PERF.md §6, PR 37)."""
    if tiers is None or len(tiers.widths) == 1:
        return (bins.T,)
    ends = list(itertools.accumulate(n for _, n in tiers.widths))
    return tuple(bins.T[tiers.order[hi - n:hi]]
                 for (_, n), hi in zip(tiers.widths, ends))


def level_histogram(bins, gpair, pos, node0, *, n_nodes: int, n_bin: int,
                    stride: int = 1, quantised: bool = False,
                    rows: Optional[RowList] = None,
                    tiers: Optional[BinTiers] = None,
                    bins_t: Optional[tuple] = None, sharded: bool = False):
    """A level's histogram for nodes ``node0 + stride*[0, n_nodes)``: the
    one way in for the level body and the page step, who branch on nothing.

    ``quantised``: ``gpair`` is the (R, C, 3) int8 limb array and the sums
    are exact int32 (ops/quantise.py); else float32, (n_nodes, F, B, C).
    ``node0`` a Python int is a constant of the program (a program a depth:
    ``build_histogram``, ``hist_accumulate_q``); a traced scalar is an operand
    of one program for every depth (``build_histogram_at``,
    ``build_histogram_q``).  ``rows``: the rows that ``pos`` places among
    these nodes, listed (the best-first pass, whose nodes may hold a few
    per cent of the page): ``build_histogram_listed``, float32 and the
    one-hot matmul only.  ``tiers``: ``bin_tiers`` of the page, for the
    float32 one-hot (the int8-limb sums take none yet; the row-pass kernels
    have no one-hot to shorten).  One tier is none: the program of a page
    whose every column needs ``n_bin`` bins is the program without tiers,
    text and cache key.  ``bins_t``: ``transposed_page`` of the same page and
    tiers, from a caller that keeps one; with it, where ``hist_form`` says
    ``onepass`` (``sharded``: the caller runs under a mesh), the level is the
    one-pass kernel, a call a tier; without it, the XLA form whatever the
    rule says, text and cache key."""
    if tiers is not None and len(tiers.widths) == 1:
        tiers = None
    widths = tier_widths(tiers, n_bin, bins.shape[1])
    if bins_t is not None and hist_form(
            n_nodes, channels=gpair.shape[1], quantised=quantised,
            listed=rows is not None, sharded=sharded,
            widths=widths) == "onepass":
        from .hist_pallas import onepass_histogram

        assert [t.shape[0] for t in bins_t] == [n for _, n in widths]
        return _untier(_by_tier([
            onepass_histogram(page, gpair, pos, node0, n_nodes=n_nodes,
                              n_bin=w, stride=stride)
            for page, (w, _) in zip(bins_t, widths)], tiers), tiers, n_bin)
    if rows is not None:
        assert not quantised and not hist_is_row_pass()
        return build_histogram_listed(bins, gpair, pos, node0, rows,
                                      n_nodes=n_nodes, n_bin=n_bin,
                                      stride=stride, tiers=tiers)
    static = isinstance(node0, int)
    if quantised:
        from .quantise import build_histogram_q, hist_accumulate_q

        assert tiers is None

        if static:
            return hist_accumulate_q(bins, gpair, pos, node0, n_nodes, n_bin,
                                     stride=stride)
        return build_histogram_q(bins, gpair, pos, node0, n_nodes=n_nodes,
                                 n_bin=n_bin, stride=stride)
    if static:
        return build_histogram(bins, gpair, pos, node0=node0, n_nodes=n_nodes,
                               n_bin=n_bin, stride=stride, tiers=tiers)
    return build_histogram_at(bins, gpair, pos, node0, n_nodes=n_nodes,
                              n_bin=n_bin, stride=stride, tiers=tiers)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bin", "stride"))
def build_histogram_multi(bins, gpair_rkc, pos_k, node0, *, n_nodes: int,
                          n_bin: int, stride: int = 1):
    """Class-batched histogram: (K, N, F, B, C) for K trees grown in
    lockstep over the SAME bins (multi:softprob one-tree-per-class).

    bins      : (R, F) int — shared binned page
    gpair_rkc : (R, K, C) f32 — per-class gradient pairs
    pos_k     : (K, R) int32 — per-class row routing
    node0     : traced scalar (padded shared level program compatible)

    The level's K histograms ride ONE jitted program (one dispatch, one
    downstream split scan — the reference's all-targets-per-pass shape,
    src/tree/hist/histogram.h:44).  On CPU the K class hists are built by
    K sequential native calls INSIDE that program rather than a fused
    row pass: a fused row-pass kernel was prototyped and measured ~40%
    SLOWER at covertype shapes (interleaving K node blocks per row blows
    the L2 working set), so it was dropped; the sequential calls keep one
    class's blocks hot and are bitwise-identical to the per-class grower
    by construction.  The XLA fallback vmaps the one-hot matmul — on the
    MXU the K axis just widens the output tile, the shape the TPU wants.
    """
    K = gpair_rkc.shape[1]
    node0 = jnp.asarray(node0, jnp.int32)
    if _host_impl() == "native":
        return jnp.stack([
            _native_hist(bins, gpair_rkc[:, k, :], pos_k[k], node0,
                         n_nodes, n_bin, stride)
            for k in range(K)])
    gpair_krc = jnp.moveaxis(gpair_rkc, 1, 0)  # (K, R, C)
    return jax.vmap(
        lambda g, p: _hist_accumulate(bins, g, p, node0, n_nodes, n_bin,
                                      2048, stride))(gpair_krc, pos_k)


def combine_sibling_hists(left, hist_prev, alive_lvl):
    """Subtraction trick assembly, shared by every grower flavour
    (updater_gpu_hist.cu:309 SubtractHist): given the built left-children
    histogram ``left`` (N/2, ...) and the parent level's ``hist_prev``
    (N/2, ...), derive each right sibling as parent - left and interleave to
    the (N, ...) level layout.  Slots whose parent did not split are zeroed
    (their "derived" hist would otherwise inherit the whole parent
    histogram).  Works for scalar (N,F,B,2) and multi-target (N,F,B,K,2)."""
    right = hist_prev - left
    N = 2 * left.shape[0]
    hist = jnp.stack([left, right], axis=1).reshape(N, *left.shape[1:])
    return hist * alive_lvl.reshape((N,) + (1,) * (hist.ndim - 1))


@functools.partial(jax.jit, static_argnames=("node0", "n_nodes"))
def node_sums(gpair, pos, *, node0: int, n_nodes: int):
    """Per-node gradient totals: (N, C) — masked segment sum.

    Used for the root sum (reference: updater_gpu_hist.cu:581 InitRoot device
    reduce followed by collective::GlobalSum).

    A reduction, not a matmul: a dot whose contraction runs over every row
    adds them to one float32 accumulator in one chain, and a long run of
    like-signed values (hessians) is then rounded the same way at every step.
    On the CPU backend that left the root hessian 0.17% high at 200k rows
    and 2.7% high at 2M; XLA's reduce sums in a tree and stays at 1e-6.
    """
    nodemask = pos[:, None] == (node0 + jnp.arange(n_nodes, dtype=pos.dtype))
    return jnp.sum(jnp.where(nodemask[:, :, None], gpair[:, None, :], 0.0),
                   axis=0)
