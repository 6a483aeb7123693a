"""Training hyper-parameters.

TPU-native re-design of the reference's DMLC parameter DSL (``TrainParam``,
src/tree/param.h:82-173; learner params src/learner.cc).  The reference builds
parameters from string key/value maps with aliases, defaults, and range
validation; we mirror that contract with dataclasses so the public dict-style
``xgb.train(params, ...)`` API keeps working, while the jitted kernels receive
a hashable, static subset.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

# alias -> canonical (reference: DMLC_DECLARE_ALIAS in src/tree/param.h)
_ALIASES = {
    "learning_rate": "eta",
    "min_split_loss": "gamma",
    "reg_lambda": "lambda",
    "reg_alpha": "alpha",
}

_CANON = {v: k for k, v in _ALIASES.items()}


def canonicalize(params: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in params.items():
        out[_ALIASES.get(k, k)] = v
    return out


@dataclasses.dataclass
class TrainParam:
    """Tree-construction parameters (reference: src/tree/param.h:82-173)."""

    eta: float = 0.3
    gamma: float = 0.0  # min_split_loss
    max_depth: int = 6
    max_leaves: int = 0
    max_bin: int = 256
    grow_policy: str = "depthwise"  # depthwise | lossguide
    min_child_weight: float = 1.0
    lambda_: float = 1.0
    alpha: float = 0.0
    max_delta_step: float = 0.0
    subsample: float = 1.0
    sampling_method: str = "uniform"  # uniform | gradient_based
    colsample_bytree: float = 1.0
    colsample_bylevel: float = 1.0
    colsample_bynode: float = 1.0
    monotone_constraints: Optional[Tuple[int, ...]] = None
    interaction_constraints: Optional[Tuple[Tuple[int, ...], ...]] = None
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 64
    refresh_leaf: bool = True

    @staticmethod
    def from_dict(params: Dict[str, Any]) -> "TrainParam":
        p = canonicalize(params)
        self = TrainParam()
        for f in dataclasses.fields(TrainParam):
            key = "lambda" if f.name == "lambda_" else f.name
            if key in p:
                v = p[key]
                if f.name == "monotone_constraints" and v is not None:
                    if isinstance(v, str):
                        v = v.strip("()[] ")
                        v = tuple(int(x) for x in v.split(",") if x.strip()) if v else None
                    else:
                        v = tuple(int(x) for x in v)
                elif f.name == "interaction_constraints" and v is not None:
                    if isinstance(v, str):
                        import json as _json

                        v = tuple(tuple(int(i) for i in grp) for grp in _json.loads(v))
                    else:
                        v = tuple(tuple(int(i) for i in grp) for grp in v)
                elif f.type == "float":
                    v = float(v)
                elif f.type == "int":
                    v = int(v)
                elif f.type == "bool":
                    v = v if isinstance(v, bool) else str(v).lower() in ("1", "true", "yes")
                setattr(self, f.name, v)
        self.validate()
        return self

    def validate(self) -> None:
        if self.max_depth < 0:
            raise ValueError("max_depth must be >= 0")
        if self.max_depth == 0 and self.max_leaves == 0:
            raise ValueError("one of max_depth / max_leaves must be positive")
        if not (0.0 < self.subsample <= 1.0):
            raise ValueError("subsample must be in (0, 1]")
        for name in ("colsample_bytree", "colsample_bylevel", "colsample_bynode"):
            v = getattr(self, name)
            if not (0.0 < v <= 1.0):
                raise ValueError(f"{name} must be in (0, 1]")
        if self.max_bin < 2:
            raise ValueError("max_bin must be >= 2")
        if self.grow_policy not in ("depthwise", "lossguide"):
            raise ValueError("grow_policy must be 'depthwise' or 'lossguide'")
        if self.sampling_method not in ("uniform", "gradient_based"):
            raise ValueError(
                "sampling_method must be 'uniform' or 'gradient_based'")

    def split_static(self) -> Tuple[float, ...]:
        """Hashable static subset consumed by the jitted split evaluator."""
        return (
            float(self.eta),
            float(self.gamma),
            float(self.min_child_weight),
            float(self.lambda_),
            float(self.alpha),
            float(self.max_delta_step),
        )


# Known learner-level keys (reference: src/learner.cc LearnerTrainParam +
# objective/metric registries); used to warn on unknown parameters like the
# reference's "Parameters: { ... } might not be used" message.
KNOWN_LEARNER_KEYS = {
    "objective", "base_score", "num_class", "eval_metric", "seed", "nthread",
    "device", "tree_method", "booster", "verbosity", "disable_default_eval_metric",
    "num_parallel_tree", "multi_strategy", "num_target",
    # dart
    "rate_drop", "one_drop", "skip_drop", "sample_type", "normalize_type",
    # gblinear
    "updater", "feature_selector", "top_k",
    # ranking
    "lambdarank_num_pair_per_sample", "lambdarank_pair_method", "ndcg_exp_gain",
    "lambdarank_unbiased", "lambdarank_bias_norm",
    "lambdarank_normalization", "lambdarank_score_normalization",
    # survival / quantile
    "aft_loss_distribution", "aft_loss_distribution_scale", "quantile_alpha",
    "expectile_alpha",
    # tweedie / huber
    "tweedie_variance_power", "huber_slope",
    "scale_pos_weight", "enable_categorical", "missing", "validate_parameters",
    "n_devices", "process_type", "refresh_leaf", "deterministic_histogram",
}


def split_unknown(params: Dict[str, Any]) -> List[str]:
    p = canonicalize(params)
    tree_keys = {("lambda" if f.name == "lambda_" else f.name) for f in dataclasses.fields(TrainParam)}
    # leading-underscore keys are internal hooks (_extmem_prefetch,
    # _lockstep, ...), deliberately outside the public surface
    return [k for k in p if k not in tree_keys
            and k not in KNOWN_LEARNER_KEYS and not k.startswith("_")]
