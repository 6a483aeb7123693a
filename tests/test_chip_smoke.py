"""chip_smoke.py without a chip, and the ``device`` parameter.

The smoke is the proof that the system starts on the chip; here, on the CPU,
it can only be shown to run to its end as a rehearsal (``--allow-cpu``, last
line ``"ok": false``), to refuse at the device phase without that option, and
never to print a result line it has not earned.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import xgboost_tpu as xtb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("device", "data", "native", "train", "histogram truth", "predict",
          "serve", "fused kernel", "cache")


def _run(script, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, script), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


def _phases(stdout):
    return [line[1:line.index("]")] for line in stdout.splitlines()
            if line.startswith("[")]


def test_smoke_rehearses_to_its_end_on_the_cpu():
    r = _run("chip_smoke.py", "--rows", "20000", "--allow-cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    assert _phases(r.stdout) == list(PHASES)
    assert "REHEARSAL ON THE CPU" in r.stdout
    last = json.loads(r.stdout.splitlines()[-1])
    assert last == {"ok": False,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_smoke_four_chip_option_runs_the_sharded_phase_alone():
    r = _run("chip_smoke.py", "--rows", "20000", "--allow-cpu", "--chips", "4",
             devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert _phases(r.stdout) == ["device", "data", "sharded layout",
                                 "sharded exact", "sharded float32"]
    assert "bitwise equal: True" in r.stdout
    last = json.loads(r.stdout.splitlines()[-1])
    assert last["ok"] is False and last["device"]["count"] == 4


@pytest.mark.parametrize("script, args", [
    ("chip_smoke.py", ()),
    ("chip_smoke.py", ("--chips", "4")),
])
def test_no_chip_no_result(script, args):
    """With no TPU the run ends at its device line: non-zero, no phase after
    it, and nothing on standard output that could be read as a result."""
    r = _run(script, *args)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert "{" not in r.stdout and _phases(r.stdout) == []


@pytest.mark.parametrize("device", ["tpu", "gpu", "cuda:0", "tpu:1"])
def test_device_tpu_raises_where_jax_found_no_tpu(device):
    """``device`` asserts where the process computes.  Asked for a TPU that
    JAX did not find, training raises and names what JAX found; it does not
    train on the CPU in silence."""
    X = np.random.default_rng(0).normal(size=(200, 4)).astype(np.float32)
    d = xtb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))
    with pytest.raises(RuntimeError, match="no 'tpu' platform.*Cpu"):
        xtb.train({"objective": "binary:logistic", "device": device}, d, 1,
                  verbose_eval=False)


def test_device_cpu_and_absent_run_on_the_cpu_and_other_ordinals_raise():
    X = np.random.default_rng(0).normal(size=(200, 4)).astype(np.float32)
    d = xtb.DMatrix(X, label=(X[:, 0] > 0).astype(np.float32))
    p = {"objective": "binary:logistic", "max_depth": 2}
    absent = xtb.train(p, d, 2, verbose_eval=False)
    stated = xtb.train({**p, "device": "cpu"}, d, 2, verbose_eval=False)
    np.testing.assert_array_equal(absent.predict(d), stated.predict(d))
    # only a device the user stated is written into the saved configuration
    assert "device" not in json.loads(
        absent.save_config())["learner"]["generic_param"]
    assert json.loads(
        stated.save_config())["learner"]["generic_param"]["device"] == "cpu"
    # cpu:1 exists on the tests' virtual mesh, but arrays are not placed on it
    with pytest.raises(RuntimeError, match="places this process's arrays"):
        xtb.train({**p, "device": "cpu:1"}, d, 1, verbose_eval=False)
    with pytest.raises(RuntimeError, match="found only"):
        xtb.train({**p, "device": "cpu:99"}, d, 1, verbose_eval=False)
