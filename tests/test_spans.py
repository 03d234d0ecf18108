"""The names the training step writes into a profiler trace
(``repro.spans``): the device phases in the compiled step's HLO
``op_name``s, and the host spans in a ``jax.profiler`` trace of the
``Trainer`` loop and of the launcher's ``--profile-dir``."""
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro import analysis, configs, engine, spans
from repro.launch import train
from repro.models import ssm

PHASE_WORDS = re.compile(r"[/()]")
METADATA = re.compile(r",? metadata=\{[^}]*\}")


def _without_metadata(hlo_text):
    """Compiled HLO text less what does not run: the source-location
    tables between the module's first line and its first computation, and
    every instruction's ``metadata={...}``."""
    head, rest = hlo_text.split("\n", 1)
    return head + METADATA.sub("", rest[re.search(r"^(ENTRY )?%", rest, re.M).start():])


def _args(mesh, executor, arch="qwen2-1.5b"):
    return train.parse_args([
        "--arch", arch, "--reduced", "--mini-batch", "4",
        "--microbatches", "2", "--seq", "32", "--mesh", mesh,
        "--executor", executor, "--calibrate", "off",
        "--remat-policy", "period", "--dtype", "bfloat16"])


def _step_hlo(mesh, executor, arch="qwen2-1.5b"):
    run = train.setup(configs.get_reduced(arch), _args(mesh, executor, arch))
    step, pipeline = run.build(run.plan)
    batch = next(iter(pipeline.batches(1)))
    lower = getattr(step, "lower", None) or step.__self__.lower_step
    return lower(run.params, run.opt_state, batch).compile().as_text()


def _name_stacks(hlo_text):
    """The words of every ``op_name`` in compiled HLO text."""
    return [set(PHASE_WORDS.split(n))
            for n in re.findall(r'op_name="([^"]*)"', hlo_text)]


@pytest.mark.parametrize("executor", ["compiled", "flat"])
def test_one_device_step_names_every_phase(executor):
    stacks = _name_stacks(_step_hlo("1:1", executor))
    for scope in (spans.TRUNK, spans.HEAD, spans.ACCUMULATE, spans.UPDATE):
        assert any(scope in s for s in stacks), scope
    assert not any(spans.GRAD_SYNC in s for s in stacks)
    assert not any(spans.SSD in s for s in stacks)  # no scan on this path
    # the directions JAX writes itself: backward, and the period remat's
    # recompute inside it, both under the trunk
    assert any({spans.TRUNK, "transpose"} <= s for s in stacks)
    assert any({spans.TRUNK, "transpose", "rematted_computation"} <= s
               for s in stacks)
    assert any({spans.HEAD, "transpose"} <= s for s in stacks)
    # JAX's own loop bodies are `while/body`: the trunk is not named so
    assert spans.TRUNK != "body"


def test_ssm_step_names_its_scan_and_the_scope_is_metadata_only(monkeypatch):
    """The SSD scan's ops carry ``ssd`` inside ``trunk``, in the forward,
    the recompute and the backward; its projections do not. (Constants the
    compiler hoists out of the loop, such as the causal mask, keep ``ssd``
    and lose ``trunk``.) Without metadata the compiled step is the same
    with the scope as without."""
    text = _step_hlo("1:1", "compiled", "mamba2-780m")
    stacks = _name_stacks(text)
    scanned = [s for s in stacks if {spans.TRUNK, spans.SSD} <= s]
    assert any("transpose" not in s for s in scanned)
    assert any({"transpose", "rematted_computation"} <= s for s in scanned)
    assert any("transpose" in s and "rematted_computation" not in s
               for s in scanned)
    assert any(spans.TRUNK in s and spans.SSD not in s for s in stacks)
    monkeypatch.setattr(ssm, "ssd_chunked", ssm.ssd_chunked.__wrapped__)
    bare = _step_hlo("1:1", "compiled", "mamba2-780m")
    assert not any(spans.SSD in s for s in _name_stacks(bare))
    assert _without_metadata(bare) == _without_metadata(text)


def test_sharded_step_names_its_gradient_all_reduce():
    text = _step_hlo("2:1", "compiled")
    reduces = [c for c in analysis.collectives(text)
               if c.kind == "all-reduce"]
    assert reduces
    for c in reduces:
        assert spans.GRAD_SYNC in PHASE_WORDS.split(c.op_name), c


def _host_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(e.name, e.duration_ns) for e in line.events]
    return out


def _trainer(**kw):
    run = train.setup(configs.get_reduced("qwen2-1.5b"),
                      _args("1:1", "compiled"))
    step, pipeline = run.build(run.plan)
    return run, engine.Trainer(step, pipeline, log_fn=None, **kw)


def test_trainer_writes_its_host_spans_into_a_trace(tmp_path):
    run, trainer = _trainer(ckpt_dir=str(tmp_path / "ckpt"))
    p, o, _ = trainer.fit(run.params, run.opt_state, 1)  # compiles
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        trainer.fit(p, o, 4, start_step=1)
    finally:
        jax.profiler.stop_trace()
    found = _host_spans(trace_dir)
    names = {n for n, _ in found}
    assert set(spans.HOST_SPANS) <= names
    assert "window" not in names
    # the input span is the interval PipelineStats.wait_s counts
    waited = sum(d for n, d in found if n == spans.INPUT) * 1e-9
    assert waited == pytest.approx(trainer.pipeline.stats.wait_s,
                                   rel=0.05, abs=2e-4 * 4)


def test_trainer_profiles_steps_after_the_first(tmp_path):
    run, trainer = _trainer(profile_dir=str(tmp_path / "prof"),
                            profile_steps=2)
    trainer.fit(run.params, run.opt_state, 5)
    assert trainer.profile_dir is None  # traced once, then off
    found = [n for n, _ in _host_spans(str(tmp_path / "prof"))]
    # steps 1 and 2 of 0..4 are traced: two dispatches
    assert found.count(spans.DISPATCH) == 2


def test_launcher_profile_dir(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    prof = tmp_path / "prof"
    train.main(["--arch", "qwen2-1.5b", "--reduced", "--steps", "3",
                "--mini-batch", "4", "--microbatches", "2", "--seq", "32",
                "--mesh", "1:1", "--calibrate", "off",
                "--profile-dir", str(prof), "--profile-steps", "1"])
    assert f"profiled to {prof}" in capsys.readouterr().out
    assert spans.DISPATCH in {n for n, _ in _host_spans(str(prof))}


@pytest.mark.parametrize("extra", [["--supervise"], ["--profile-steps", "0"]])
def test_launcher_refuses_profile_options(extra):
    with pytest.raises(SystemExit):
        train.parse_args(["--arch", "qwen2-1.5b", "--profile-dir", "d"]
                         + extra)
