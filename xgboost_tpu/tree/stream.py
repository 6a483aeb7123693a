"""Streaming (external-memory) tree grower.

Reference: the reference's external-memory training re-streams compressed
Ellpack pages from the host cache through every BuildHist pass
(updater_gpu_hist.cu:597 GetBatches inside the driver loop; prefetch window
sparse_page_source.h:293).  Here each level makes ONE pass over the host
pages: a page's rows are routed with the PREVIOUS level's split decisions and
immediately accumulated into the current level's histogram, so the page is
touched once per level; host->HBM transfer of page i+1 overlaps compute on
page i (jax.device_put is async).

Everything except the page loop reuses the in-core grower's pieces
(grow.decide_level / _update_positions / ops.histogram.level_histogram), so
the split semantics are bitwise identical to HistTreeGrower.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.histogram import combine_sibling_hists, level_histogram
from ..ops.split import SplitParams
from .grow import (TreeState, _update_positions, decide_level, init_tree_state,
                   make_set_matrix, max_nodes_for_depth)


def _sim_transfer_ms_per_mb() -> float:
    """Test hook: XTB_EXTMEM_SIM_TRANSFER_MS_PER_MB injects a synthetic
    per-byte transfer latency into _put_page (see comment there)."""
    import os

    try:
        return float(os.environ.get("XTB_EXTMEM_SIM_TRANSFER_MS_PER_MB", "0"))
    except ValueError:
        return 0.0


@functools.partial(
    jax.jit,
    static_argnames=("node0_prev", "n_prev", "node0", "n_nodes", "n_bin",
                     "has_prev", "has_cat", "build", "stride", "quantised"),
)
def _page_step(page_bins, gpair_seg, pos_seg, prev_best, prev_can, *,
               node0_prev: int, n_prev: int, node0: int, n_nodes: int,
               n_bin: int, has_prev: bool, has_cat: bool, build: bool = True,
               stride: int = 1, quantised: bool = False):
    """Route one page with the previous level's splits, then accumulate the
    current level's histogram over it (stride=2: left children only, for the
    subtraction trick).  quantised: gpair_seg carries (T, C, 3) int8 limbs
    and the histogram is exact int32 (ops/quantise.py)."""
    if has_prev:
        pos_seg = _update_positions(page_bins, pos_seg, prev_best, prev_can,
                                    node0_prev, n_prev, n_bin, has_cat)
    if build:
        hist = level_histogram(page_bins, gpair_seg, pos_seg, node0,
                               n_nodes=n_nodes, n_bin=n_bin, stride=stride,
                               quantised=quantised)
    else:
        hist = jnp.zeros((n_nodes, 1, 1, 2), jnp.float32)
    return pos_seg, hist


@functools.partial(
    jax.jit, static_argnames=("depth", "params", "lossguide", "last_level"),
)
def _decide_level(state: TreeState, hist, n_bins, cuts_pad, feature_mask,
                  set_matrix, cat_mask, *, depth: int, params: SplitParams,
                  lossguide: bool, last_level: bool):
    """evaluate + record for one level (no position update — pages do that)."""
    return decide_level(
        state, lambda alive_lvl: (None, hist), cuts_pad, n_bins, feature_mask,
        set_matrix, cat_mask, (1 << depth) - 1, 1 << depth, params=params,
        last_level=last_level, lossguide=lossguide,
        has_cat=bool(cat_mask.shape) and cat_mask.shape[0] > 0)[:3]


class StreamingHistTreeGrower:
    """Grow one tree over host-resident binned pages (ExtMemQuantileDMatrix)."""

    def __init__(self, max_depth: int, params: SplitParams, *,
                 interaction_sets=None, max_leaves: int = 0,
                 lossguide: bool = False, mesh=None,
                 distributed: bool = False, prefetch: bool = True,
                 quantised: bool = False, page_skip: bool = False) -> None:
        self.max_depth = max_depth
        self.params = params
        self.interaction_sets = interaction_sets
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        # multi-device: pages are row-sharded over the mesh at transfer time
        # and GSPMD partitions the histogram matmul (hist reduce = the XLA
        # collective the reference gets from NCCL AllReduceHist); page rows
        # are PAGE_ALIGN(=1024)-aligned so every shard is equal
        self.mesh = mesh
        # multi-process: every process streams its own page shard; the
        # accumulated level histogram crosses processes once per level
        # (the AllReduceHist of the reference's extmem path —
        # updater_gpu_hist.cu:601 runs unchanged under rabit there)
        self.distributed = distributed
        # prefetch=False serializes decompress/H2D against device compute
        # (measurement baseline for the overlap gain; reference knob:
        # n_prefetch_batches=0, sparse_page_source.h:293)
        self.prefetch = prefetch
        # fixed-point limb histograms (ops/quantise.py): page accumulation,
        # chip psum and the cross-process reduce are exact integer sums, so
        # external-memory training is bit-identical on any topology too
        self.quantised = quantised
        # gradient-based sampling decides page residency (arXiv:2005.09148
        # §5): a page whose every row was sampled out (zero gpair) is
        # skipped by all D per-level passes and routed ONCE at the end —
        # page traffic per tree drops from D loads to 1 for sampled-out
        # pages.  Enabled by core.py only under
        # sampling_method=gradient_based (docs/extmem.md).
        self.page_skip = page_skip
        self.max_nodes = max_nodes_for_depth(max_depth)

    def _put_page(self, page_np):
        sim_active = _sim_transfer_ms_per_mb() > 0.0
        if (self.mesh is None and not sim_active
                and jax.default_backend() == "cpu"):
            # CPU backend: "device" memory IS host memory, so re-staging the
            # same immutable page every level just burns memcpy — keep the
            # committed array (budgeted LRU beside the decompress cache).
            # On TPU this cache must NOT exist (streaming exists because
            # HBM cannot hold the pages), and the simulated-transfer
            # harness disables it to preserve TPU-like streaming.
            from ..data.extmem import device_page_cache_get_or_put

            return device_page_cache_get_or_put(
                page_np, lambda: jax.device_put(
                    np.ascontiguousarray(page_np)))
        arr = np.ascontiguousarray(page_np)
        if self.mesh is None:
            out = jax.device_put(arr)
        else:
            from ..parallel.mesh import row2d_sharding

            out = jax.device_put(arr, row2d_sharding(self.mesh))
        sim = _sim_transfer_ms_per_mb()
        if sim > 0.0:
            # Simulated H2D latency (VERDICT r4 #6): a sleep proportional to
            # page bytes stands in for the DMA the CPU backend doesn't have.
            # sleep yields the core, so XLA's async-dispatched page compute
            # proceeds underneath exactly like device compute under a real
            # transfer — making overlap_gain measurable without TPU.
            import time

            time.sleep(arr.nbytes / 1e6 * sim / 1e3)
        return out

    def grow(self, pages: List, page_offsets: List[int], gpair, valid,
             cuts_pad, n_bins, feature_masks=None, cat_mask=None) -> TreeState:
        F = pages[0].shape[1]
        B = cuts_pad.shape[1]
        has_cat = cat_mask is not None
        cm = jnp.asarray(cat_mask) if has_cat else jnp.zeros(0, bool)
        setmat = jnp.asarray(make_set_matrix(self.interaction_sets, F))
        ones = jnp.ones((1, F), dtype=bool)
        state = init_tree_state(
            gpair, valid, max_nodes=self.max_nodes,
            n_sets=setmat.shape[0],
            max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0,
            n_bin=B,
        )
        n_pages = len(pages)
        # ---- page residency (gradient-based sampling, arXiv:2005.09148):
        # pages whose every row carries zero gpair (sampled out) leave the
        # per-level streaming entirely; their positions are routed once at
        # the end so margin updates stay exact.  Decided on the RAW gpair
        # (before limb quantisation).  At least one page stays resident so
        # a fully-sampled-out rank still joins every per-level allreduce.
        stream_idx = list(range(n_pages))
        skipped_idx: List[int] = []
        if self.page_skip and n_pages > 1:
            row_mass = jnp.sum(jnp.abs(gpair),
                               axis=tuple(range(1, gpair.ndim)))
            page_ids = jnp.asarray(np.repeat(
                np.arange(n_pages), np.diff(np.asarray(page_offsets))))
            pmass = np.asarray(jax.ops.segment_sum(
                row_mass, page_ids, num_segments=n_pages))
            active = pmass > 0.0
            if not active.any():
                active[0] = True
            stream_idx = [i for i in range(n_pages) if active[i]]
            skipped_idx = [i for i in range(n_pages) if not active[i]]
        rho = None
        if self.quantised:
            from ..ops.quantise import prepare_quantised

            gpair, rho, state = prepare_quantised(
                gpair, valid, state, distributed=self.distributed)
        elif self.distributed:
            from .grow import sync_root_totals

            state = sync_root_totals(state)
        from ..data import extmem as _extmem

        events = (_extmem.PAGE_EVENT_LOG if _extmem.event_log_enabled()
                  else None)
        prev_best, prev_can, prev_d = None, None, -1
        hist_prev = None
        decisions = []  # (best, can, depth) per split level, for the replay
        for d in range(self.max_depth + 1):
            build = d < self.max_depth  # last level only finalizes leaves
            subtract = build and d > 0 and hist_prev is not None
            node0 = (1 << d) - 1
            N = 1 << d
            n_build = (N // 2) if subtract else N
            hist_acc = None
            # prefetch pipeline (data/extmem.py PageScheduler): pages
            # decode/stage on the shared worker pool N ahead of the
            # consumer, so the host-side decompress of page j+1..j+N
            # overlaps page j's (async-dispatched) device compute
            if events is not None:
                events.append(("level", d))
            sched = _extmem.PageScheduler(
                [pages[i] for i in stream_idx], self._put_page,
                lookahead=None if self.prefetch else 0, events=events)
            pos = state.pos
            try:
                for j, i in enumerate(stream_idx):
                    dev = sched.get(j)
                    lo, hi = page_offsets[i], page_offsets[i + 1]
                    seg_len = hi - lo
                    pos_seg = lax.dynamic_slice_in_dim(pos, lo, seg_len)
                    gp_seg = lax.dynamic_slice_in_dim(gpair, lo, seg_len)
                    pos_seg, h = _page_step(
                        dev, gp_seg, pos_seg, prev_best, prev_can,
                        node0_prev=(1 << prev_d) - 1 if prev_d >= 0 else 0,
                        n_prev=1 << max(prev_d, 0), node0=node0,
                        n_nodes=n_build, n_bin=B,
                        has_prev=prev_best is not None, has_cat=has_cat,
                        build=build, stride=2 if subtract else 1,
                        quantised=self.quantised,
                    )
                    if not self.prefetch and j + 1 < len(stream_idx):
                        # serialize: page j's compute must finish before
                        # page j+1's host decompress starts (pos_seg too —
                        # on the last level h is a constant dummy while the
                        # position routing still runs)
                        jax.block_until_ready((pos_seg, h))
                    pos = lax.dynamic_update_slice_in_dim(pos, pos_seg, lo,
                                                          axis=0)
                    if build:
                        hist_acc = h if hist_acc is None else hist_acc + h
            finally:
                # on an abort (fault-injected decode, compute error) the
                # not-yet-started prefetch futures must not keep loading
                sched.close()
            state = state._replace(pos=pos)
            fm = ones if feature_masks is None else feature_masks(d, N)
            if hist_acc is not None and self.distributed:
                # one cross-process exchange per level, after the local page
                # accumulation and before the sibling subtraction
                if self.quantised:
                    from ..ops.quantise import allreduce_limbs

                    hist_acc = allreduce_limbs(hist_acc)
                else:
                    from .. import collective

                    hist_acc = jnp.asarray(
                        collective.allreduce(np.asarray(hist_acc)))
            if hist_acc is None:  # last level: dummy hist, leaves only
                hist_acc = jnp.zeros((N, F, B, 2), jnp.float32)
            elif subtract:
                # SubtractHist: right sibling = parent - left (grow.level_step)
                # — exact in limb space when quantised (integer subtract)
                alive_lvl = lax.dynamic_slice_in_dim(state.alive, node0, N)
                hist_acc = combine_sibling_hists(hist_acc, hist_prev, alive_lvl)
            if build:
                hist_prev = hist_acc
            if self.quantised and build:
                from ..ops.quantise import dequantise

                hist_f = dequantise(hist_acc, rho)  # the ONE rounding step
            else:
                hist_f = hist_acc
            state, best, can = _decide_level(
                state, hist_f, n_bins, cuts_pad, fm, setmat, cm,
                depth=d, params=self.params, lossguide=self.lossguide,
                last_level=(d == self.max_depth),
            )
            if best is not None:
                decisions.append((best, can, d))
            prev_best, prev_can, prev_d = best, can, d
        if skipped_idx:
            state = self._route_skipped(state, pages, page_offsets, gpair,
                                        skipped_idx, decisions, B, has_cat,
                                        events)
        return state

    def _route_skipped(self, state, pages, page_offsets, gpair, skipped_idx,
                       decisions, B, has_cat, events):
        """One final pass over the sampled-out pages: replay every level's
        split decisions so their rows' positions (and so their leaf margin
        updates) are identical to a run that streamed them every level —
        D page loads collapse to 1 for pages sampling removed."""
        if events is not None:
            events.append(("route_skipped", len(skipped_idx)))
        from ..data import extmem as _extmem

        sched = _extmem.PageScheduler(
            [pages[i] for i in skipped_idx], self._put_page,
            lookahead=None if self.prefetch else 0, events=events)
        pos = state.pos
        try:
            for j, i in enumerate(skipped_idx):
                dev = sched.get(j)
                lo, hi = page_offsets[i], page_offsets[i + 1]
                seg_len = hi - lo
                pos_seg = lax.dynamic_slice_in_dim(pos, lo, seg_len)
                gp_seg = lax.dynamic_slice_in_dim(gpair, lo, seg_len)
                for best, can, d in decisions:
                    pos_seg, _ = _page_step(
                        dev, gp_seg, pos_seg, best, can,
                        node0_prev=(1 << d) - 1, n_prev=1 << d, node0=0,
                        n_nodes=1, n_bin=B, has_prev=True, has_cat=has_cat,
                        build=False, quantised=self.quantised,
                    )
                pos = lax.dynamic_update_slice_in_dim(pos, pos_seg, lo,
                                                      axis=0)
        finally:
            sched.close()
        state = state._replace(pos=pos)
        return state

    @staticmethod
    def to_host(state: TreeState):
        from .grow import HistTreeGrower

        return HistTreeGrower.to_host(state)

