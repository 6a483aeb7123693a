"""Multi-chip tree grower: shard_map(level_step) + psum histograms.

The distributed design mirrors the reference exactly at the semantic level
(SURVEY §3.4): every shard builds full-width histograms over its row shard,
one ``lax.psum`` replaces AllReduceHist (src/tree/gpu_hist/histogram.cu:598),
and the split decision is computed redundantly-but-identically on every shard
(deterministic f32 psum -> bitwise-identical trees per shard, the property the
reference gets from quantised integer allreduce).  No tracker, no sockets:
the mesh is the communicator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.split import SplitParams
from ..tree.grow import (HistTreeGrower, TreeState, init_tree_state,
                         level_step, level_step_padded, level_width,
                         make_set_matrix, max_nodes_for_depth)
from .mesh import DATA_AXIS


def _state_specs(data_axis: str):
    """PartitionSpecs for TreeState: pos is row-sharded, tree arrays replicated."""
    return TreeState(
        pos=P(data_axis),
        alive=P(), totals=P(), feat=P(), sbin=P(), thr=P(), dleft=P(),
        is_leaf=P(), leaf_val=P(), gain=P(), base_weight=P(), sum_hess=P(),
        lower=P(), upper=P(), setcompat=P(), splits_left=P(),
        is_cat=P(), cat_set=P(),
    )


class ShardedHistTreeGrower(HistTreeGrower):
    """Drop-in replacement for HistTreeGrower over a 1-D mesh: its loop and
    its width rule, each level program wrapped in ``shard_map``."""

    sharded = True

    def __init__(self, max_depth: int, params: SplitParams, mesh, *,
                 interaction_sets=None, max_leaves: int = 0,
                 lossguide: bool = False, quantised: bool = False) -> None:
        # quantised: int psum is exact, so trees are bitwise-identical for
        # ANY chip count, and prepare_quantised runs as a jit over the
        # already-sharded gpair: GSPMD's all-reduce-max and integer root
        # reduce are exact, so rho and the root totals are identical on
        # every topology
        super().__init__(max_depth, params, interaction_sets=interaction_sets,
                         max_leaves=max_leaves, lossguide=lossguide,
                         quantised=quantised)
        self.mesh = mesh
        self._built_for = None

    def _build(self, n_features: int, n_bin: int = 1, has_cat: bool = False) -> None:
        if self._built_for == (n_features, n_bin, has_cat):
            return
        ax = DATA_AXIS
        sspec = _state_specs(ax)
        n_sets = make_set_matrix(self.interaction_sets, n_features).shape[0]

        self._init_fn = jax.jit(
            jax.shard_map(
                functools.partial(
                    init_tree_state, max_nodes=self.max_nodes, axis_name=ax,
                    n_sets=n_sets, n_bin=n_bin,
                    max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0,
                ),
                mesh=self.mesh,
                in_specs=(P(ax, None), P(ax)),
                out_specs=sspec,
            )
        )

        q = self.quantised
        # quantised: the gpair slot carries (R, C, 3) int8 limbs and every
        # level fn takes a trailing replicated rho (per-channel scale)
        gspec = P(ax, None, None) if q else P(ax, None)
        row_specs = (sspec, P(ax, None), gspec, P(), P(), P(), P(), P())
        rho_specs = (P(),) if q else ()

        def program(step, n_more: int, **static):
            # after the rows' operands: hist_prev (replicated: psummed at
            # its own level; None where a level takes none) and, for the
            # shared program, node0.  The hist psum rides inside via
            # axis_name.
            return jax.jit(jax.shard_map(
                functools.partial(
                    step, params=self.params, axis_name=ax,
                    lossguide=self.lossguide, has_cat=has_cat, quantised=q,
                    **static),
                mesh=self.mesh,
                in_specs=row_specs + (P(),) * n_more + rho_specs,
                out_specs=(sspec, P())))

        md = self.max_depth
        # a shared interior program a width tier, as the loop asks for them
        self._interior_fns = {
            w: program(level_step_padded, 2, width=w, subtract=True)
            for w in sorted({level_width(d, md) for d in range(1, md)})}
        self._level_fns = {
            d: program(level_step, 1, depth=d, last_level=(d == md),
                       subtract=(0 < d < md))
            for d in range(md + 1)}
        self._built_for = (n_features, n_bin, has_cat)

    def _init_state(self, gpair, valid, setmat, cuts_pad,
                    has_cat: bool) -> TreeState:
        self._build(setmat.shape[1], cuts_pad.shape[1], has_cat)
        return self._init_fn(gpair, valid)

    def _run_level(self, d: int, width, state, page, fm, setmat, cm,
                   hist_prev, rho, has_cat: bool, tiers=None, bins_t=None):
        # a mesh's levels build one tier (core.py) in the XLA form
        assert tiers is None and bins_t is None
        rho_args = () if rho is None else (rho,)
        if width is not None:
            return self._interior_fns[width](
                state, *page, fm, setmat, cm, hist_prev,
                jnp.int32((1 << d) - 1), *rho_args)
        return self._level_fns[d](state, *page, fm, setmat, cm, hist_prev,
                                  *rho_args)


class ShardedMultiTargetGrower:
    """Vector-leaf trees over a 1-D mesh: shard_map(level_step_multi) with
    the 2K-channel histogram crossing shards in one psum (the multi-target
    AllReduceHist; reference: MultiTargetHistBuilder under rabit,
    src/tree/updater_quantile_hist.cc:156)."""

    def __init__(self, max_depth: int, params: SplitParams, n_targets: int,
                 mesh, *, max_leaves: int = 0, lossguide: bool = False) -> None:
        self.max_depth = max_depth
        self.params = params
        self.n_targets = n_targets
        self.mesh = mesh
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        self.max_nodes = max_nodes_for_depth(max_depth)
        self._built_for = None

    def _state_specs(self, ax):
        from ..tree.grow_multi import MultiTreeState

        return MultiTreeState(
            pos=P(ax), alive=P(), totals=P(), feat=P(), sbin=P(), thr=P(),
            dleft=P(), is_leaf=P(), leaf_val=P(), gain=P(), base_weight=P(),
            sum_hess=P(), splits_left=P(),
        )

    def _build(self, n_features: int, n_bin: int) -> None:
        if self._built_for == (n_features, n_bin):
            return
        from ..tree.grow_multi import init_multi_state, level_step_multi

        ax = DATA_AXIS
        sspec = self._state_specs(ax)
        self._init_fn = jax.jit(
            jax.shard_map(
                functools.partial(
                    init_multi_state, max_nodes=self.max_nodes,
                    n_targets=self.n_targets, axis_name=ax,
                    max_splits=(self.max_leaves - 1) if self.max_leaves > 0 else 0,
                ),
                mesh=self.mesh,
                in_specs=(P(ax, None, None), P(ax)),
                out_specs=sspec,
            )
        )
        md = self.max_depth
        # hist_prev is replicated (already psummed at its own level), and
        # None at the root and on the last level
        row_specs = (sspec, P(ax, None), P(ax, None, None), P(), P(), P(), P())
        self._level_fns = {
            d: jax.jit(jax.shard_map(
                functools.partial(
                    level_step_multi, depth=d, params=self.params,
                    last_level=(d == md), n_targets=self.n_targets,
                    subtract_on=(0 < d < md), axis_name=ax,
                    lossguide=self.lossguide),
                mesh=self.mesh, in_specs=row_specs, out_specs=(sspec, P())))
            for d in range(md + 1)}
        self._built_for = (n_features, n_bin)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None):
        F = bins.shape[1]
        self._build(F, cuts_pad.shape[1])
        ones = jnp.ones((1, F), dtype=bool)
        state = self._init_fn(gpair, valid)
        hist = None
        for d in range(self.max_depth + 1):
            fm = ones if feature_masks is None else feature_masks(d, 1 << d)
            state, hist = self._level_fns[d](
                state, bins, gpair, cuts_pad, n_bins, fm,
                None if d == self.max_depth else hist)
        return state

    @staticmethod
    def to_host(state):
        from ..tree.grow_multi import MultiTargetTreeGrower

        return MultiTargetTreeGrower.to_host(state)
