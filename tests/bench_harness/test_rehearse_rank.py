"""The command itself on the ranking cell, in a process of its own on the
CPU, as test_rehearse.py runs it on ``higgs-d6.train``."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def command(*more, cache):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        script = json.load(fh)["command"][1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, script), "--workload",
         "mslr-web30k-ndcg.train", "--seed", "3000000019", "--seconds", "0.2",
         *more], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_no_tpu_no_result_line(tmp_path):
    done = command("--trace", "0", cache=tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_traced_rehearsal_ends_with_correct_false_and_the_cpu_in_device(tmp_path):
    done = command("--trace", "1", "--rehearse-rows", "4000", cache=tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert len(lines) == 1
    result = json.loads(lines[0])
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # no CPU number under a device metric's name
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared_within_limits"] is True
    assert "query groups of" in done.stderr and "held-out NDCG@10" in done.stderr
    tail = done.stderr.strip().splitlines()[-len(result["compared"]):]
    for row, name in zip(tail, result["compared"]):
        assert row.split()[0] == name and "limit" in row
