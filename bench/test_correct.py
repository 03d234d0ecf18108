"""The comparison that decides ``correct``, on the CPU at a tiny size.

* a sound run of the harness (set-up steps, window, reference) is correct;
* the control, the float32 reference computed in float8, is not;
* each fault a training cell can have, planted in the program's timed path
  underneath the harness, makes the run incorrect: a step that returns its
  state unchanged, half of the batch left out (the mean taken over the
  rest), and the gradient exchange between devices left out.

The limits here are the tiny size's own, set between the sound runs'
readings and the control's the way ``bench/limits`` are set at the cells'
sizes on the chip.
"""
from __future__ import annotations

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness

ROOT = harness.ROOT
# From CPU readings at this size on seeds 3, 5, 6 and 2**31 + 11, as
# (largest sound run, smallest control): qwen2 loss_gap (9.1e-5, 5.4e-4),
# grad_gap (3.5e-3, 1.07e-2), update_gap (2.8e-3, 2.1e-2); the half-batch
# fault reads 0.4 or more on both leaf gaps. At this size the mamba2
# control does not separate (grad_gap 7.8e-3 against 9.0e-3 for a sound
# run), so mamba2 has a sound-run test only: the reference agrees with the
# program (loss_gap 4.0e-4, grad_gap 9.0e-3, update_gap 5.8e-3 at most).
TINY_LIMITS = {
    "qwen2": {"loss_gap": 2.5e-4, "grad_gap": 6e-3, "update_gap": 8e-3},
    "mamba2": {"loss_gap": 1e-3, "grad_gap": 2e-2, "update_gap": 2e-2},
}
SEEDS = (3, 2 ** 31 + 11)


def tiny_cell(config: str, mesh: str = "1:1", chips: int = 1,
              mini: int = 8, n_micro: int = 2) -> harness.Cell:
    with open(os.path.join(ROOT, "bench", "testdata", config + "-tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    traffic = {"seq": 32, "mini_batch": mini, "num_microbatches": n_micro,
               "mesh": mesh, "remat": "period", "tokens": "uniform",
               "optimizer": {"kind": "sgd", "lr": 0.05, "momentum": 0.9,
                             "weight_decay": 0.0005}}
    ref = importlib.import_module("bench.reference." + cfg["reference"])
    return harness.Cell(f"{config}-tiny.{mesh}", chips, cfg, traffic, ref,
                        man["end_to_end"], [], ROOT)


def run(cell, seed=SEEDS[0]):
    return harness.run(cell, seed, 0.05, False, allow_cpu=True,
                       limits=TINY_LIMITS[cell.cfg["reference"]])


@pytest.mark.parametrize("config,mesh,chips,seed", [
    ("qwen2", "1:1", 1, SEEDS[1]), ("mamba2", "1:1", 1, SEEDS[0]),
    ("qwen2", "4:1", 4, SEEDS[0])])
def test_sound_run_is_correct(config, mesh, chips, seed):
    res = run(tiny_cell(config, mesh, chips), seed)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res["checks"]) == list(correct.NUMBERS)
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_control_fails_the_limits():
    cell = tiny_cell("qwen2")
    devices = harness.devices_for(1, allow_cpu=True)
    for seed in SEEDS:
        ref = harness.reference_readings(cell, seed, devices)
        ctl = harness.reference_readings(cell, seed, devices, mode="fp8")
        ok, checks = correct.judge(correct.gaps(ctl, ref), TINY_LIMITS["qwen2"])
        assert not ok, checks


def _unchanged_state(monkeypatch):
    step = harness.Program._timed_step

    def timed_step(self, params, opt_state, batch):
        copies = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, metrics = step(self, *copies, batch)
        return params, opt_state, metrics

    monkeypatch.setattr(harness.Program, "_timed_step", timed_step)


def _half_batch(monkeypatch):
    """Every mini-batch's second half replaced by its first: the program
    then averages over the first half alone."""
    state = harness.Program.state

    class FirstHalf:
        def __init__(self, data):
            self.data = data

        def batch(self, n, step):
            b = self.data.batch(n, step)
            return {k: np.concatenate([v[:n // 2], v[:n // 2]])
                    for k, v in b.items()}

    def patched(self, seed):
        out = state(self, seed)
        self.pipeline.dataset = FirstHalf(self.pipeline.dataset)
        return out

    monkeypatch.setattr(harness.Program, "state", patched)


def _no_exchange(monkeypatch):
    from repro.engine import sharded
    monkeypatch.setattr(sharded, "psum_flat", lambda tree, axes: tree)


FAULTS = {"unchanged_state": (_unchanged_state, "1:1", 1),
          "half_batch": (_half_batch, "1:1", 1),
          "no_exchange": (_no_exchange, "4:1", 4)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_caught(fault, monkeypatch):
    plant, mesh, chips = FAULTS[fault]
    if len(jax.devices()) < chips:
        raise RuntimeError(f"{fault} needs {chips} host devices; set "
                           "--xla_force_host_platform_device_count")
    cell = tiny_cell("qwen2", mesh, chips)
    plant(monkeypatch)
    res = run(cell)
    assert not res["correct"], res["checks"]
