"""Sharding/dry-run integration: the production-mesh lowering path runs in a
subprocess (the 512-device XLA flag must be set before jax initializes) with
REDUCED configs — proves mesh construction, the sharding policy, jit
lowering and compile end-to-end without waiting on full-size compiles."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(arch, shape, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--reduced", "--no-probe", *extra]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1200,
                          cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("qwen2-1.5b", "train_4k"),
    ("mamba2-780m", "decode_32k"),
])
def test_reduced_dryrun_single_pod(arch, shape):
    res = _run(arch, shape)
    assert res["num_devices"] == 256
    assert res["memory"]["temp_bytes"] >= 0
    assert res["raw_cost_analysis"]["flops"] > 0


@pytest.mark.slow
def test_reduced_dryrun_multi_pod():
    res = _run("qwen2-1.5b", "train_4k", extra=("--multi-pod",))
    assert res["num_devices"] == 512
    assert res["axes"] == ["pod", "data", "model"]
