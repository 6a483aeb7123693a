"""Job kind ``train-rank`` on the CPU at a size a test run can hold, with
the gradient in the form the chip runs (the CPU's native kernel gated off
where the objective asks for it): a sound run reads within the limits, the
bfloat16 control and each fault planted in the program do not.  Driven as
``run_cell`` drives a job, as test_job_train.py drives ``train``."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

ROWS = 6000
WORKLOAD = "mslr-web30k-ndcg.train"


@pytest.fixture(autouse=True)
def chip_form(monkeypatch):
    from xgboost_tpu.objective import ranking

    monkeypatch.setattr(ranking, "_native_lambdarank_ok", lambda: False)


def drive(seed=7, seconds=0.05, **compare_kw):
    cell = run.load_cell(WORKLOAD)
    job = run.load_module("jobs", cell["traffic"]["job"])
    env = {"log": lambda s: None, "rehearse_rows": ROWS}
    state = job.setup(cell, seed, env)
    job.window(state, seconds)
    numbers = job.compare(state, env, **compare_kw)
    rows = run.judge(numbers, run.load_limits(cell["traffic"]["job"]))
    return state, numbers, {r[0]: r[3] for r in rows}


def test_a_sound_run_reads_within_every_limit_and_gives_the_readers_their_clocks():
    state, numbers, within = drive()
    assert all(within.values()), (numbers, within)
    assert state.failed == 0 and state.attempted >= 1
    sizes = np.bincount(state.qid)
    assert state.rows == sizes.sum() <= ROWS and len(sizes) > 20
    c = state.clocks
    assert {"dmatrix_s", "round_s", "traced_round_s", "round_max_s", "rows",
            "row_rounds", "opened_at", "window_s"} <= set(c)
    assert c["rows"] == c["rank_docs"] == state.rows
    assert c["row_rounds"] == state.rows * state.attempted
    assert 0 < c["rank_pairs"] <= np.sum(np.minimum(sizes, 32) * sizes)
    # ranking on the float64 walk instead: the hazard is read, not judged
    assert numbers["grad_gap_rank64"] < 1e-4 and "grad_gap_rank64" not in within


def test_the_control_in_bfloat16_is_not_correct():
    _, numbers, _ = drive(lower_precision=True)
    limits = run.load_limits("train-rank")
    control = {k[:-4]: v for k, v in numbers.items() if k.endswith("_low")}
    assert set(control) == {"hess_gap", "grad_gap", "split_gap"}
    assert control["hess_gap"] > limits["hess_gap"]
    assert control["grad_gap"] > limits["grad_gap"]
    assert not all(r[3] for r in run.judge({**numbers, **control}, limits))


def test_the_faults_in_the_references_place_are_not_correct():
    _, numbers, _ = drive(faults=True)
    limits = run.load_limits("train-rank")
    for tag in ("_tail", "_nonorm", "_stale"):
        fault = {k[:-len(tag)]: v for k, v in numbers.items() if k.endswith(tag)}
        assert {"hess_gap", "grad_gap"} <= set(fault), tag
        assert fault["hess_gap"] > 10 * limits["hess_gap"], tag
        assert fault["grad_gap"] > 10 * limits["grad_gap"], tag
    assert numbers["bin_mass_gap_half"] > limits["bin_mass_gap"]
    assert numbers["margin_gap_stale"] > limits["margin_gap"]


def last_documents_left_out(monkeypatch):
    """A group's documents past the fortieth never reach the grid."""
    from xgboost_tpu.objective import ranking

    real = ranking.make_topk_layout

    def short(*args):
        layout = real(*args)
        return layout._replace(count=np.minimum(layout.count, 40))

    monkeypatch.setattr(ranking, "make_topk_layout", short)


def no_group_normalisation(monkeypatch):
    """The group's log2(1 + S) / S rescale left out of the program."""
    from xgboost_tpu.objective import ranking

    real = ranking._lambda_gradients_topk
    monkeypatch.setattr(
        ranking, "_lambda_gradients_topk",
        lambda pred, layout, **kw: real(pred, layout,
                                        **{**kw, "group_norm": False}))


def gradient_one_round_stale(monkeypatch):
    """Every round boosts on the gradient of the round before."""
    from xgboost_tpu.objective import ranking

    real = ranking._LambdaRankBase.get_gradient
    kept = {}

    def stale(self, *a, **kw):
        now = real(self, *a, **kw)
        out = kept.get("pair", now)
        kept["pair"] = now
        return out

    monkeypatch.setattr(ranking._LambdaRankBase, "get_gradient", stale)


@pytest.mark.parametrize("fault", [last_documents_left_out,
                                   no_group_normalisation,
                                   gradient_one_round_stale],
                         ids=lambda f: f.__name__)
def test_a_fault_planted_in_the_program_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    _, numbers, within = drive()
    over = {k for k, ok in within.items() if not ok}
    assert {"hess_gap", "grad_gap"} <= over, (numbers, over)
