"""What a run of the train job leaves in the program's span ring, for the
tests that read per-layer metrics from a context built by hand."""
import time

import numpy as np
import pytest

WARM, TRACED, ROUNDS = 2, 2, 6  # the traffic file's warm and traced rounds


@pytest.fixture
def run_ring():
    """Empties the ring, then one QuantileDMatrix and one train of ROUNDS
    rounds on the CPU; returns the host clocks around them."""
    import xgboost_tpu as xtb
    from xgboost_tpu.callback import TrainingCallback
    from xgboost_tpu.telemetry import flight

    flight.clear()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    t0 = time.perf_counter()
    d = xtb.QuantileDMatrix(X, label=y, max_bin=32)
    dmatrix_s = time.perf_counter() - t0
    ends = []

    class Clock(TrainingCallback):
        def after_iteration(self, model, epoch, evals_log):
            ends.append(time.perf_counter())
            return False

    xtb.train({"objective": "binary:logistic", "max_depth": 3, "max_bin": 32},
              d, ROUNDS, verbose_eval=False, callbacks=[Clock()])
    return {"warm": WARM, "traced": TRACED, "rounds": ROUNDS,
            "dmatrix_s": dmatrix_s,
            "round_s": [float(t) for t in np.diff(ends)[WARM + TRACED - 1:]]}


@pytest.fixture(autouse=True)
def _ring_for_contexts_built_by_hand(request):
    """test_trace_and_work.py reads every per-layer metric of the manifest
    from a context it builds by hand; three of them read the program's span
    ring beside it, so the ring gets what a run leaves there."""
    if request.module.__name__.endswith("test_trace_and_work"):
        request.getfixturevalue("run_ring")
