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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.split import SplitParams
from ..telemetry import span
from ..tree.grow import (TreeState, init_tree_state, level_step,
                         level_step_padded, make_set_matrix,
                         max_nodes_for_depth)
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


class ShardedHistTreeGrower:
    """Drop-in replacement for HistTreeGrower over a 1-D mesh."""

    def __init__(self, max_depth: int, params: SplitParams, mesh, *,
                 hist_impl: str = "xla", interaction_sets=None,
                 max_leaves: int = 0, lossguide: bool = False,
                 quantised: bool = False) -> None:
        self.max_depth = max_depth
        self.params = params
        self.mesh = mesh
        self.hist_impl = hist_impl
        self.interaction_sets = interaction_sets
        self.max_leaves = max_leaves
        self.lossguide = lossguide
        # fixed-point limb histograms (ops/quantise.py): int psum is exact,
        # so trees are bitwise-identical for ANY chip count — the
        # GradientQuantiser contract (src/tree/gpu_hist/quantiser.cuh)
        self.quantised = quantised
        self.max_nodes = max_nodes_for_depth(max_depth)
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
        self._level_fns = {}
        # one shared padded interior program for all depths 1..max_depth-1
        # (same compile-wall fix as HistTreeGrower; hist psum rides inside
        # level_step_padded via axis_name) — per-depth programs only for the
        # root and the leaf-finalize level, plus the pallas fallback.
        # Same platform rule as HistTreeGrower (shared helper).
        from ..tree.grow import default_padded_levels

        self._padded = (self.hist_impl != "pallas" and self.max_depth >= 2
                        and default_padded_levels(self.max_depth))
        if self._padded:
            W = 1 << (self.max_depth - 1)
            pad_base = functools.partial(
                level_step_padded, width=W, params=self.params, axis_name=ax,
                hist_impl=self.hist_impl, lossguide=self.lossguide,
                has_cat=has_cat, subtract=True, quantised=q,
            )
            self._interior_fn = jax.jit(
                jax.shard_map(pad_base, mesh=self.mesh,
                              in_specs=row_specs + (P(), P()) + rho_specs,
                              out_specs=(sspec, P()))
            )
        depths = ((0, self.max_depth) if self._padded
                  else range(self.max_depth + 1))
        for d in depths:
            last = d == self.max_depth
            subtract = d > 0 and not last and not self._padded
            base = functools.partial(
                level_step,
                depth=d,
                params=self.params,
                last_level=last,
                axis_name=ax,
                hist_impl=self.hist_impl,
                lossguide=self.lossguide,
                has_cat=has_cat,
                subtract=subtract,
                quantised=q,
            )
            if last:
                # hist neither consumed nor produced on the last level
                def fn(state, bins, gpair, cuts, nb, fm, sm, cmm, *r, _b=base):
                    st, _ = _b(state, bins, gpair, cuts, nb, fm, sm, cmm)
                    return st

                in_specs, out_specs = row_specs + rho_specs, sspec
            elif subtract:
                # hist_prev is replicated (already psummed at its own level)
                fn = base
                in_specs = row_specs + (P(),) + rho_specs
                out_specs = (sspec, P())
            else:
                if q:
                    def fn(state, bins, gq, cuts, nb, fm, sm, cmm, rho,
                           _b=base):
                        return _b(state, bins, gq, cuts, nb, fm, sm, cmm,
                                  None, rho)
                else:
                    fn = base
                in_specs = row_specs + rho_specs
                out_specs = (sspec, P())
            self._level_fns[d] = jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                              out_specs=out_specs)
            )
        self._built_for = (n_features, n_bin, has_cat)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None,
             cat_mask=None) -> TreeState:
        F = bins.shape[1]
        self._build(F, cuts_pad.shape[1], has_cat=cat_mask is not None)
        ones = jnp.ones((1, F), dtype=bool)
        setmat = jnp.asarray(make_set_matrix(self.interaction_sets, F))
        cm = jnp.asarray(cat_mask) if cat_mask is not None else jnp.zeros(F, bool)
        state = self._init_fn(gpair, valid)
        rho_args = ()
        if self.quantised:
            from ..ops.quantise import prepare_quantised

            # jit over the already-sharded gpair: GSPMD's all-reduce-max and
            # integer root reduce are exact, so rho and the root totals are
            # identical on every topology
            gpair, rho, state = prepare_quantised(gpair, valid, state)
            rho_args = (rho,)
        # same fused-level span name as HistTreeGrower (each sharded level
        # program is hist psum + split eval + position rewrite in one call)
        _LEVEL = "grow.build_hist+eval_split"
        if self._padded:
            from ..tree.grow import HistTreeGrower

            md = self.max_depth
            W = 1 << (md - 1)
            fm = ones if feature_masks is None else feature_masks(0, 1)
            with span(_LEVEL, depth=0):
                state, hist = self._level_fns[0](state, bins, gpair, cuts_pad,
                                                 n_bins, fm, setmat, cm,
                                                 *rho_args)
            hist_pad = jnp.zeros((W,) + hist.shape[1:],
                                 hist.dtype).at[:1].set(hist)
            for d in range(1, md):
                fm = (ones if feature_masks is None
                      else HistTreeGrower._pad_mask(feature_masks(d, 1 << d), W))
                with span(_LEVEL, depth=d):
                    state, hist_pad = self._interior_fn(
                        state, bins, gpair, cuts_pad, n_bins, fm, setmat, cm,
                        hist_pad, jnp.int32((1 << d) - 1), *rho_args)
            fm = ones if feature_masks is None else feature_masks(md, 1 << md)
            with span(_LEVEL, depth=md):
                state = self._level_fns[md](state, bins, gpair, cuts_pad,
                                            n_bins, fm, setmat, cm, *rho_args)
            return state
        hist_prev = None
        for d in range(self.max_depth + 1):
            fm = ones if feature_masks is None else feature_masks(d, 1 << d)
            with span(_LEVEL, depth=d):
                if d == self.max_depth:
                    state = self._level_fns[d](state, bins, gpair, cuts_pad,
                                               n_bins, fm, setmat, cm,
                                               *rho_args)
                elif d == 0:
                    state, hist_prev = self._level_fns[d](state, bins, gpair,
                                                          cuts_pad, n_bins, fm,
                                                          setmat, cm,
                                                          *rho_args)
                else:
                    state, hist_prev = self._level_fns[d](state, bins, gpair,
                                                          cuts_pad, n_bins, fm,
                                                          setmat, cm,
                                                          hist_prev,
                                                          *rho_args)
        return state

    @staticmethod
    def to_host(state: TreeState):
        from ..tree.grow import HistTreeGrower

        return HistTreeGrower.to_host(state)


class ShardedMultiTargetGrower:
    """Vector-leaf trees over a 1-D mesh: shard_map(level_step_multi) with
    the 2K-channel histogram crossing shards in one psum (the multi-target
    AllReduceHist; reference: MultiTargetHistBuilder under rabit,
    src/tree/updater_quantile_hist.cc:156)."""

    def __init__(self, max_depth: int, params: SplitParams, n_targets: int,
                 mesh, *, max_leaves: int = 0, lossguide: bool = False) -> None:
        from ..tree.grow_multi import MultiTreeState  # noqa: F401

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
        self._level_fns = {}
        for d in range(self.max_depth + 1):
            last = d == self.max_depth
            subtract = d > 0 and not last
            base = functools.partial(
                level_step_multi, depth=d, params=self.params,
                last_level=last, n_targets=self.n_targets,
                subtract_on=subtract, axis_name=ax, lossguide=self.lossguide,
            )
            row_specs = (sspec, P(ax, None), P(ax, None, None), P(), P(), P())
            if last:
                def fn(state, bins, gpair, cuts, nb, fm, _b=base):
                    st, _ = _b(state, bins, gpair, cuts, nb, fm)
                    return st

                in_specs, out_specs = row_specs, sspec
            elif subtract:
                fn, in_specs, out_specs = base, row_specs + (P(),), (sspec, P())
            else:
                fn, in_specs, out_specs = base, row_specs, (sspec, P())
            self._level_fns[d] = jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                              out_specs=out_specs)
            )
        self._built_for = (n_features, n_bin)

    def grow(self, bins, gpair, valid, cuts_pad, n_bins, feature_masks=None):
        F = bins.shape[1]
        self._build(F, cuts_pad.shape[1])
        ones = jnp.ones((1, F), dtype=bool)
        state = self._init_fn(gpair, valid)
        hist_prev = None
        for d in range(self.max_depth + 1):
            fm = ones if feature_masks is None else feature_masks(d, 1 << d)
            if d == self.max_depth:
                state = self._level_fns[d](state, bins, gpair, cuts_pad,
                                           n_bins, fm)
            elif d == 0:
                state, hist_prev = self._level_fns[d](state, bins, gpair,
                                                      cuts_pad, n_bins, fm)
            else:
                state, hist_prev = self._level_fns[d](state, bins, gpair,
                                                      cuts_pad, n_bins, fm,
                                                      hist_prev)
        return state

    @staticmethod
    def to_host(state):
        from ..tree.grow_multi import MultiTargetTreeGrower

        return MultiTargetTreeGrower.to_host(state)
