"""Multi-process (multi-host) tree grower.

The reference's distributed training (SURVEY §3.4; dask/__init__.py:722
_train_async -> rabit allreduce inside each updater) keeps every worker on
its own row shard and reduces exactly three things: the root gradient sum,
the per-level histograms, and eval metrics.  This grower reproduces that
shape for the multi-*process* case: each process runs the jitted device
pieces (histogram build, split decide, position update — shared with the
in-core growers) on its local rows, and the fixed-size histogram crosses
processes through ``collective.allreduce`` between the build and decide
steps, the role NCCL allreduce plays in updater_gpu_hist.cu:598.  The root
gradient sum is reduced here too; eval metrics are globalized in
``Booster.eval_set`` (shard gather), so early stopping stays in lockstep.

Within a process the single-chip path is used; combine with the shard_map
grower by giving each process its own chip(s) (process-level DP x chip-level
DP).  Determinism: the host allreduce is an ordered f32 sum over the gathered
(world, ...) stack, so every process sees bitwise-identical histograms and
grows bitwise-identical trees — the property the reference engineers via
quantised integer allreduce (quantiser.cuh).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import collective
from ..ops.histogram import combine_sibling_hists
from ..reliability.faults import maybe_inject
from ..ops.split import SplitParams, cat_columns
from ..tree.grow import (TreeState, init_tree_state, make_set_matrix,
                         max_nodes_for_depth)
from ..tree.stream import _decide_level, _page_step


class ProcessHistTreeGrower:
    """Drop-in for HistTreeGrower when jax.process_count() > 1 (or when the
    host-level collective is initialized for a CPU multi-process test)."""

    def __init__(self, max_depth: int, params: SplitParams, *,
                 interaction_sets=None, max_leaves: int = 0,
                 lossguide: bool = False, subtract: bool = True,
                 mesh=None, quantised: bool = False) -> None:
        self.max_depth = max_depth
        self.params = params
        self.interaction_sets = interaction_sets
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        self.subtract = subtract
        # process-DP x chip-DP composition (the reference's multi-host rabit
        # x per-device NCCL layering, src/collective/comm.cuh:51; dask one-
        # GPU-per-worker generalized): rows are sharded over the process's
        # LOCAL mesh, GSPMD partitions the jitted page step (hist partials
        # psum over local chips), and the replicated local hist then crosses
        # processes through the ordered host allreduce below.
        self.mesh = mesh
        # fixed-point limb histograms (ops/quantise.py): the chip psum and
        # the cross-process reduction both run on exact integers, so trees
        # are bitwise-identical across ANY process x chip topology — the
        # reference's GradientQuantiser + integer-rabit contract
        # (src/tree/gpu_hist/quantiser.cuh)
        self.quantised = quantised
        self.max_nodes = max_nodes_for_depth(max_depth)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None,
             cat_mask=None) -> TreeState:
        F = bins.shape[1]
        B = cuts_pad.shape[1]
        has_cat = cat_columns(cat_mask)
        setmat = jnp.asarray(make_set_matrix(self.interaction_sets, F))
        ones = jnp.ones((1, F), dtype=bool)
        state = init_tree_state(
            gpair, valid, max_nodes=self.max_nodes, n_sets=setmat.shape[0],
            max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0,
            n_bin=B,
        )
        if self.mesh is not None:
            # chip-level row sharding within this process; jit/GSPMD then
            # partitions _page_step (position update stays elementwise-
            # sharded, the hist contraction all-reduces over local chips)
            from .mesh import row_sharding, shard_rows

            bins, gpair = shard_rows(self.mesh, bins, gpair)
            state = state._replace(
                pos=jax.device_put(state.pos, row_sharding(self.mesh)))
        # root totals: GlobalSum across processes (updater_gpu_hist.cu:581)
        from ..tree.grow import sync_root_totals

        rho = None
        if self.quantised:
            from ..ops.quantise import prepare_quantised

            gpair, rho, state = prepare_quantised(gpair, valid, state,
                                                  distributed=True)
        else:
            state = sync_root_totals(state)

        prev_best, prev_can, prev_d = None, None, -1
        hist_prev = None
        for d in range(self.max_depth + 1):
            build = d < self.max_depth
            subtract = self.subtract and build and d > 0 and hist_prev is not None
            node0 = (1 << d) - 1
            N = 1 << d
            n_build = (N // 2) if subtract else N
            pos, h = _page_step(
                bins, gpair, state.pos, prev_best, prev_can,
                node0_prev=(1 << prev_d) - 1 if prev_d >= 0 else 0,
                n_prev=1 << max(prev_d, 0), node0=node0, n_nodes=n_build,
                n_bin=B, has_prev=prev_best is not None, has_cat=has_cat,
                build=build, stride=2 if subtract else 1,
                quantised=self.quantised,
            )
            state = state._replace(pos=pos)
            if build:
                # seam: the per-level histogram exchange — delay a rank
                # (straggler), raise (failed allreduce -> signal_error),
                # or kill (death inside the collective, the case the
                # tracker's EOF abort fan-out exists for)
                maybe_inject("process.allreduce", rank=collective.get_rank)
                # the one cross-process exchange per level (AllReduceHist);
                # quantised: limbs reduce in int64 on host — exact, so the
                # exchange is order-invariant (integer-rabit role)
                if self.quantised:
                    from ..ops.quantise import allreduce_limbs, dequantise

                    hist = allreduce_limbs(h)
                else:
                    hist = jnp.asarray(collective.allreduce(np.asarray(h)))
                if subtract:
                    alive_lvl = jax.lax.dynamic_slice_in_dim(
                        state.alive, node0, N)
                    hist = combine_sibling_hists(hist, hist_prev, alive_lvl)
                hist_prev = hist
                hist_f = (dequantise(hist, rho) if self.quantised else hist)
            else:
                hist_f = jnp.zeros((N, F, B, 2), jnp.float32)
            fm = ones if feature_masks is None else feature_masks(d, N)
            state, best, can = _decide_level(
                state, hist_f, n_bins, cuts_pad, fm, setmat,
                depth=d, params=self.params, lossguide=self.lossguide,
                last_level=(d == self.max_depth), has_cat=has_cat,
            )
            prev_best, prev_can, prev_d = best, can, d
        return state

    @staticmethod
    def to_host(state: TreeState):
        from ..tree.grow import HistTreeGrower

        return HistTreeGrower.to_host(state)
