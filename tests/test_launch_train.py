"""The training launcher's own entry point, ``launch/train.py:main``, run
end to end on the CPU, and the helpers it shares with ``chip_smoke.py``
(compile cache, device memory budget, mesh axis types)."""
import math

import jax
import pytest
from jax.sharding import AxisType

from repro import configs
from repro.core import memory_model
from repro.launch import compile_cache, mesh as mesh_lib, train
from repro.models import nn


@pytest.mark.parametrize("mesh,executor", [
    ("1:1", "compiled"), ("1:1", "flat"), ("1:1", "streaming"),
    ("2:1", "flat"),  # ShardedExecutor, one all-reduce per mini-batch
    ("1:2", "compiled"),  # PipelinedExecutor, 1F1B over two stages
])
def test_main_trains_two_steps_on_a_host_mesh(mesh, executor, monkeypatch,
                                              tmp_path):
    # with the variable set the launcher leaves the cache to JAX, which
    # read it (unset) at import: this test writes no compile cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    last = train.main([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "2",
        "--mini-batch", "4", "--microbatches", "2", "--seq", "32",
        "--mesh", mesh, "--executor", executor, "--calibrate", "off",
        "--log-every", "1"])
    assert math.isfinite(last["loss"])
    # random init over a 512-token vocabulary: the loss sits near ln(512)
    assert abs(last["loss"] - math.log(512)) < 0.2


@pytest.mark.parametrize("mesh", ["2:1", "1:2"])
def test_host_mesh_state_starts_replicated(mesh):
    """The shard_map executors take the state replicated; it is created
    there, not on one device whose copy would outlive the first step."""
    args = train.parse_args(["--arch", "qwen2-1.5b", "--reduced",
                             "--mini-batch", "4", "--microbatches", "2",
                             "--seq", "32", "--mesh", mesh])
    run = train.setup(configs.get_reduced("qwen2-1.5b"), args)
    devices = set(run.mesh.devices.flat)
    for leaf in jax.tree.leaves((run.params, run.opt_state)):
        assert leaf.sharding.is_fully_replicated
        assert leaf.sharding.device_set == devices


def test_meshes_have_auto_axes():
    """Sharding hints (``with_sharding_constraint``) need Auto axes;
    ``jax.make_mesh`` defaults to Explicit ones."""
    for mesh in (mesh_lib.make_host_mesh(1, 1),
                 mesh_lib.make_host_mesh(1, 1, pod=1)):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_shard_hint_reads_the_ambient_mesh():
    mesh = mesh_lib.make_host_mesh(1, 1)
    assert nn.auto_axes() == {}
    with jax.set_mesh(mesh):
        assert nn.auto_axes() == {"data": 1, "model": 1}
        assert nn.mesh_axis_size("model") == 1
        y = jax.jit(lambda x: nn.shard_hint(x, "data", None))(
            jax.numpy.ones((2, 3)))
    assert y.shape == (2, 3)


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == compile_cache.CACHE_DIR
    assert path.endswith(".jax_cache")


class _Device:
    device_kind = "fake"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_bytes_limit():
    assert memory_model.device_bytes_limit(
        _Device({"bytes_limit": 123, "bytes_in_use": 0})) == 123
    for stats in (None, {"bytes_in_use": 0}):
        with pytest.raises(RuntimeError, match="no memory limit"):
            memory_model.device_bytes_limit(_Device(stats))


def test_budget_off_the_chip_is_the_planning_default():
    args = train.parse_args(["--arch", "qwen2-1.5b"])
    assert train.budget_bytes(args) is None  # plan_mbs: V5E_HBM_BYTES
    args = train.parse_args(["--arch", "qwen2-1.5b", "--hbm-budget-gb", "2"])
    assert train.budget_bytes(args) == 2 * 1024 ** 3
