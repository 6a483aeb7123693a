"""The categorical cell's command in a process of its own on the CPU, as
the benchmark calls it with ``--rehearse-rows``: the whole path, traced,
ends with ``"correct": false``, every compared number within its limit,
``catset_gap`` among them, and the job's log names the categorical
splits."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_the_rehearsal_runs_the_categorical_path(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        script = json.load(fh)["command"][1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, script), "--workload",
         "airline-cat-d6.train", "--seed", "3000000019", "--seconds", "0.2",
         "--trace", "1", "--rehearse-rows", "40000"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["metrics"] == {}
    assert result["compared_within_limits"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert "catset_gap" in result["compared"]
    said = [line for line in done.stderr.splitlines()
            if "categorical splits:" in line]
    assert said and "the widest categorical column 300 bins" in said[0]
