"""deterministic_histogram: fixed-point limb histograms (ops/quantise.py).

The reference makes gpu_hist bitwise reproducible across worker topologies
by quantising gradients to integers so every reduction is exact
(src/tree/gpu_hist/quantiser.cuh; tests/cpp/tree/test_gpu_hist.cu
determinism cases).  These tests pin the same contract for the TPU design:
int8-limb one-hot matmuls with int32 accumulation, psum over integers,
int64 host allreduce — identical tree bits for ANY chip/process layout.
"""
import hashlib
import threading

import numpy as np
import pytest

import xgboost_tpu as xtb
from xgboost_tpu import collective


def _data(n=3000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) * 1.5 + np.nan_to_num(X[:, 1]) > 0).astype(
        np.float32)
    return X, y


def _dump_hash(bst):
    return hashlib.md5(
        "".join(bst.get_dump(dump_format="json")).encode()).hexdigest()


def test_quantised_hist_matches_int64_reference():
    """The limb histogram must equal an exact int64 reconstruction."""
    import jax.numpy as jnp

    from xgboost_tpu.ops.quantise import (QUANT_BITS, hist_accumulate_q,
                                          local_rho, quantise_gpair)

    rng = np.random.default_rng(3)
    R, F, B, N = 5000, 4, 16, 4
    bins = rng.integers(0, B + 1, size=(R, F)).astype(np.int32)  # B = missing
    gpair = rng.normal(size=(R, 2)).astype(np.float32)
    pos = rng.integers(-1, N, size=R).astype(np.int32)
    valid = np.ones(R, bool)

    rho = local_rho(jnp.asarray(gpair), jnp.asarray(valid))
    gq = np.asarray(quantise_gpair(jnp.asarray(gpair), rho))
    hist = np.asarray(hist_accumulate_q(
        jnp.asarray(bins), jnp.asarray(gq), jnp.asarray(pos),
        jnp.int32(0), N, B, chunk=512), np.int64)

    # exact integer reference from the limbs
    q = (gq[:, :, 0].astype(np.int64) + 256 * gq[:, :, 1].astype(np.int64)
         + 65536 * gq[:, :, 2].astype(np.int64))
    ref = np.zeros((N, F, B, 2), np.int64)
    for n in range(N):
        sel = pos == n
        for f in range(F):
            for b in range(B):
                m = sel & (bins[:, f] == b)
                ref[n, f, b] = q[m].sum(axis=0)
    got = (hist[..., 0] + 256 * hist[..., 1] + 65536 * hist[..., 2])
    np.testing.assert_array_equal(got, ref)
    # quantisation error bounded by one step of the fixed-point grid per
    # channel (half a step from rounding + up to half from the f32 g*scale
    # product itself)
    step = np.asarray(rho) / ((1 << QUANT_BITS) - 1)
    recon = q * step[None, :].astype(np.float64)
    assert (np.abs(recon - gpair) <= 1.0001 * step[None, :]).all()


def test_quantised_pallas_kernel_bitwise_matches_xla():
    """The int8 x int8 -> int32 Pallas kernel (interpret mode off-TPU) must
    produce bitwise-identical limb histograms to the XLA accumulation —
    integer sums are exact, so ANY disagreement is a bug, not noise."""
    import jax.numpy as jnp

    from xgboost_tpu.ops.hist_pallas import build_histogram_pallas_q
    from xgboost_tpu.ops.quantise import (hist_accumulate_q, local_rho,
                                          quantise_gpair)

    rng = np.random.default_rng(11)
    R, F, B, N = 2500, 5, 16, 4
    bins = rng.integers(0, B + 1, size=(R, F)).astype(np.int32)
    gpair = rng.normal(size=(R, 2)).astype(np.float32)
    valid = np.ones(R, bool)
    rho = local_rho(jnp.asarray(gpair), jnp.asarray(valid))
    gq = quantise_gpair(jnp.asarray(gpair), rho)
    for node0, n_nodes, stride in ((0, N, 1), (N - 1, N // 2, 2)):
        pos = jnp.asarray(
            rng.integers(node0 - 1, node0 + 2 * n_nodes, size=R), jnp.int32)
        ref = np.asarray(hist_accumulate_q(
            jnp.asarray(bins), gq, pos, jnp.int32(node0), n_nodes, B,
            chunk=512, stride=stride))
        got = np.asarray(build_histogram_pallas_q(
            jnp.asarray(bins), gq, pos, node0=node0, n_nodes=n_nodes,
            n_bin=B, stride=stride, interpret=True, row_tile=512,
            feat_group=2))
        np.testing.assert_array_equal(got, ref)


def test_quantised_bitwise_across_device_counts(eight_devices):
    """1 device vs 8-chip mesh: identical tree bits (the f32 path only
    guarantees this structurally at shallow depth)."""
    X, y = _data()

    def run(nd):
        bst = xtb.train({"objective": "binary:logistic", "max_depth": 5,
                         "eta": 0.3, "max_bin": 64, "n_devices": nd,
                         "deterministic_histogram": True},
                        xtb.DMatrix(X, label=y), 4, verbose_eval=False)
        return _dump_hash(bst), bst.predict(xtb.DMatrix(X))

    h1, p1 = run(1)
    h8, p8 = run(8)
    assert h1 == h8
    np.testing.assert_array_equal(p1, p8)


def test_quantised_bitwise_process_times_chip(eight_devices):
    """2 fake processes x 4-chip mesh vs 2 fake processes x 1 chip: the full
    composed topology must produce the same bits as the flat one — the
    cross-TOPOLOGY guarantee the f32 default cannot give (see
    test_multiprocess.py::test_two_process_chip_mesh_composed_identical)."""
    X, y = _data()
    results, errors = {}, {}

    def worker(rank, nd, tag):
        try:
            with collective.CommunicatorContext(
                    dmlc_communicator="in-memory", in_memory_world_size=2,
                    in_memory_rank=rank, in_memory_group=f"quant-{tag}"):
                Xs, ys = X[rank::2], y[rank::2]
                bst = xtb.train({"objective": "binary:logistic",
                                 "max_depth": 4, "eta": 0.3, "max_bin": 64,
                                 "n_devices": nd,
                                 "deterministic_histogram": True},
                                xtb.DMatrix(Xs, label=ys), 3,
                                verbose_eval=False)
                results[(tag, rank)] = _dump_hash(bst)
        except Exception as e:  # noqa: BLE001
            errors[(tag, rank)] = e

    for tag, nd in (("mesh", 4), ("flat", 1)):
        ts = [threading.Thread(target=worker, args=(r, nd, tag))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in ts), "worker deadlocked"
    assert not errors, errors
    # ranks agree within a topology AND the topologies agree with each other
    assert results[("mesh", 0)] == results[("mesh", 1)]
    assert results[("flat", 0)] == results[("flat", 1)]
    assert results[("mesh", 0)] == results[("flat", 0)]


def test_quantised_quality_matches_f32():
    """Fixed-point resolution (22 bits of the max-gradient scale) must not
    cost accuracy."""
    X, y = _data(seed=7)
    Xt, yt = _data(seed=8)

    def err(det):
        bst = xtb.train({"objective": "binary:logistic", "max_depth": 5,
                         "eta": 0.3, "max_bin": 64,
                         "deterministic_histogram": det},
                        xtb.DMatrix(X, label=y), 6, verbose_eval=False)
        return np.mean((bst.predict(xtb.DMatrix(Xt)) > 0.5) != yt)

    e_q, e_f = err(True), err(False)
    assert e_q <= e_f + 0.01, (e_q, e_f)


def test_quantised_unsupported_combinations_raise():
    X, y = _data(n=500)
    d = xtb.DMatrix(X, label=y)
    with pytest.raises(NotImplementedError):
        xtb.train({"deterministic_histogram": True, "tree_method": "exact",
                   "objective": "binary:logistic"}, d, 1, verbose_eval=False)
    with pytest.raises(NotImplementedError):
        xtb.train({"deterministic_histogram": True, "grow_policy": "lossguide",
                   "max_leaves": 8, "max_depth": 0,
                   "objective": "binary:logistic"}, d, 1, verbose_eval=False)


def test_quantised_extmem_bitwise_across_device_counts(eight_devices):
    """External-memory streaming x deterministic_histogram: page-order,
    chip-count, and process-count all reduce in exact integers, so extmem
    training is bit-identical across topologies too."""
    from xgboost_tpu.data.extmem import DataIter, ExtMemQuantileDMatrix

    X, y = _data(n=4096)

    class Pages(DataIter):
        def __init__(self):
            super().__init__()
            self._i = 0

        def next(self, input_data):
            if self._i >= 4:
                return 0
            lo = self._i * 1024
            input_data(data=X[lo:lo + 1024], label=y[lo:lo + 1024])
            self._i += 1
            return 1

        def reset(self):
            self._i = 0

    def run(nd):
        d = ExtMemQuantileDMatrix(Pages(), max_bin=32)
        bst = xtb.train({"objective": "binary:logistic", "max_depth": 4,
                         "eta": 0.3, "max_bin": 32, "n_devices": nd,
                         "deterministic_histogram": True}, d, 3,
                        verbose_eval=False)
        return _dump_hash(bst)

    assert run(1) == run(8)


def test_quantised_extmem_process_times_chip(eight_devices):
    """Extmem streaming under 2 fake processes x chips: the distributed
    quantised branches (rho MAX allreduce, per-level limb allreduce,
    quantised root) must keep topologies bit-identical, mirroring the
    in-memory composed test."""
    from xgboost_tpu.data.extmem import DataIter, ExtMemQuantileDMatrix

    X, y = _data(n=4096)
    results, errors = {}, {}

    def make_iter(Xs, ys):
        class Pages(DataIter):
            def __init__(self):
                super().__init__()
                self._i = 0

            def next(self, input_data):
                if self._i >= 2:
                    return 0
                lo = self._i * (len(ys) // 2)
                hi = lo + len(ys) // 2
                input_data(data=Xs[lo:hi], label=ys[lo:hi])
                self._i += 1
                return 1

            def reset(self):
                self._i = 0

        return Pages()

    def worker(rank, nd, tag):
        try:
            with collective.CommunicatorContext(
                    dmlc_communicator="in-memory", in_memory_world_size=2,
                    in_memory_rank=rank, in_memory_group=f"qext-{tag}"):
                d = ExtMemQuantileDMatrix(make_iter(X[rank::2], y[rank::2]),
                                          max_bin=32)
                bst = xtb.train({"objective": "binary:logistic",
                                 "max_depth": 3, "eta": 0.3, "max_bin": 32,
                                 "n_devices": nd,
                                 "deterministic_histogram": True}, d, 2,
                                verbose_eval=False)
                results[(tag, rank)] = _dump_hash(bst)
        except Exception as e:  # noqa: BLE001
            errors[(tag, rank)] = e

    for tag, nd in (("mesh", 4), ("flat", 1)):
        ts = [threading.Thread(target=worker, args=(r, nd, tag))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in ts), "worker deadlocked"
    assert not errors, errors
    assert results[("mesh", 0)] == results[("mesh", 1)]
    assert results[("flat", 0)] == results[("flat", 1)]
    assert results[("mesh", 0)] == results[("flat", 0)]
