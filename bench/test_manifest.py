"""``BENCHMARK.json`` and the files under ``bench/`` name each other: every
configuration, traffic mix, cell and per-layer metric has its file, and
every such file has an entry. A new one needs a file and an entry and no
edit to the harness. Each reference's ``flops_per_token`` is checked
against a count by hand at a tiny size."""
from __future__ import annotations

import importlib
import json
import os
import shutil

import pytest

from bench import correct, harness

ROOT = harness.ROOT
BENCH = os.path.join(ROOT, "bench")


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def stems(sub, ext):
    return {f[:-len(ext)] for f in os.listdir(os.path.join(BENCH, sub))
            if f.endswith(ext)}


def test_top_level():
    m = manifest()
    assert list(m) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert m["command"] == ["python3", "bench/run.py"] and m["paths"] == ["bench"]
    assert {e["name"] for e in m["end_to_end"]} >= {"setup_s", "tokens_per_s"}
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e
        assert set(metric["workloads"]) <= cells


def test_every_entry_has_its_file_and_every_file_its_entry():
    m = manifest()
    confs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        importlib.import_module("bench.reference." + cfg["reference"])
    assert stems("configs", ".json") == set(confs)
    assert stems("traffic", ".json") == {w["traffic"] for w in m["workloads"]}
    assert stems("limits", ".json") == {w["name"] for w in m["workloads"]}
    assert stems("metrics", ".py") == {x["name"] for x in m["per_layer"]}
    for w in m["workloads"]:
        assert w["config"] in confs
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert set(correct.load_limits(ROOT, w["name"])) == set(correct.NUMBERS)
        assert {x["name"] for x in cell.per_layer} == {
            x["name"] for x in m["per_layer"] if w["name"] in x["workloads"]}


def test_a_new_cell_and_metric_need_only_files_and_entries(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest()
    base = m["workloads"][0]
    with open(root / "bench" / "traffic" / (base["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic["seq"] = 4096
    (root / "bench" / "traffic" / "seq4096.new.json").write_text(json.dumps(traffic))
    (root / "bench" / "limits" / "new.cell.json").write_text(
        json.dumps({n: 1e-3 for n in correct.NUMBERS}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.tokens_per_s\n")
    m["workloads"].append(dict(base, name="new.cell", traffic="seq4096.new"))
    m["per_layer"].append({"name": "new_metric", "unit": "tokens/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "model step", "moves": "tokens_per_s",
                           "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    cell = harness.load_cell("new.cell", root=str(root))
    assert cell.traffic["seq"] == 4096
    assert [x["name"] for x in cell.per_layer] == ["new_metric"]
    read = harness._metric_reader(str(root), "new_metric")
    assert read(type("Ctx", (), {"tokens_per_s": 3.0})) == 6.0


def _tiny(name):
    with open(os.path.join(BENCH, "testdata", name + "-tiny.json")) as f:
        return json.load(f)


def test_flops_per_token_by_hand():
    from bench.reference import mamba2, qwen2
    # qwen2 tiny: d 64, 4 heads of 16 (2 kv heads), ffn 128, vocab 256,
    # 2 layers, seq 32. Per layer q 64x64, k and v 64x32, o 64x64, MLP
    # 3 x 64x128 = 36,864 weights; with the tied head 64x256, N = 90,112.
    # 6N = 540,672; attention 12 * 2 layers * 4 heads * 16 * 32 = 49,152.
    assert qwen2.flops_per_token(_tiny("qwen2"), 32) == 540672 + 49152
    # mamba2 tiny: d 64, inner 128, state 16, 8 heads of 16, conv 4,
    # vocab 256, 2 layers, chunk 8. Per layer in_proj 64 x (256+32+8),
    # conv 4 x 160, out_proj 128x64 = 27,776; N = 2 * 27,776 + 16,384 =
    # 71,936 and 6N = 431,616. SSD per token and layer, forward:
    # C.B 2*8*16 + output 2*8*8*16 + state and read-out 4*8*16*16 =
    # 10,496, times 3 (forward and backward) and 2 layers = 62,976.
    assert mamba2.flops_per_token(_tiny("mamba2"), 32) == 431616 + 62976


@pytest.mark.parametrize("kind", ["TPU v5 lite"])
def test_peaks_table(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    assert peaks["kinds"][kind]["bf16_flops"] == 197e12
    assert peaks["kinds"][kind]["hbm_bytes_per_s"] == 819e9


def test_unknown_device_and_platform_are_errors():
    dev = type("Dev", (), {"platform": "tpu", "device_kind": "TPU v99"})
    with pytest.raises(KeyError, match="TPU v99"):
        harness.peak_of(dev)
    dev.platform = "cpu"
    with pytest.raises(harness.NoChip, match="TPU only"):
        harness.peak_of(dev)
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.devices_for(1)
