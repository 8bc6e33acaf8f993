"""A configuration, a cell and a per-layer metric are added as new files:
the harness lists and loads them, and no file that was there changes."""
from __future__ import annotations

import hashlib
import json

import pytest
from chipbench_util import CHIP_DIR, REPO, tiny_copy

from bench import compare as C
from bench.harness import Ctx
from bench.registry import BenchmarkError, Registry


def digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_workload_and_metric_are_new_files(tmp_path):
    before = digests(CHIP_DIR)
    registry = tiny_copy(tmp_path)
    chip = registry.dir
    (chip / "metrics" / "tiny.rounds.py").write_text(
        "def read(ctx):\n    return ctx.tokens_per_s\n")
    bench = json.loads(registry.benchmark_path.read_text())
    bench["per_layer"].append({
        "name": "tiny.rounds", "unit": "tokens/s", "better": "higher",
        "source": "program_counter", "layer": "round step",
        "moves": "train_tokens_per_s", "workloads": ["tiny.cohort"]})
    registry.benchmark_path.write_text(json.dumps(bench))
    registry = Registry(chip, registry.benchmark_path)

    after = digests(chip)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        p.relative_to(chip) for p in (chip / "configs" / "tiny.json",
                                      chip / "workloads" / "tiny.cohort.json",
                                      chip / "metrics" / "tiny.rounds.py")}
    assert "tiny" in registry.config_names()
    assert "tiny.cohort" in registry.workload_names()
    assert "tiny.rounds" in registry.metric_names()
    cell = registry.cell("tiny.cohort")
    assert cell["config_file"]["hidden_size"] == 64
    assert cell["workload"]["round"]["clients"] == 4
    readers = {m["name"]: m["reader"] for m in registry.per_layer(
        "tiny.cohort")}
    assert "round.mfu" in readers
    ctx = Ctx(trace={}, config={}, workload={}, peak={}, chips=1,
              tokens_per_s=123.0, registry=registry)
    assert readers["tiny.rounds"].read(ctx) == 123.0


def test_every_cell_config_and_metric_of_the_benchmark_loads():
    registry = Registry()
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell["workload"]["chips"] == w["chips"]
        assert cell["workload"]["traffic"] == w["traffic"]
        for key in C.NAMES:
            assert cell["workload"]["limits"][key] > 0
    for c in bench["configs"]:
        config = json.loads((REPO / c["file"]).read_text())
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
    for m in bench["per_layer"]:
        assert callable(registry.per_layer(m["workloads"][0])[0]["reader"]
                        .read)


def test_unknown_cell_and_device_kind_raise():
    registry = Registry()
    with pytest.raises(BenchmarkError, match="no cell"):
        registry.cell("no-such-cell")
    with pytest.raises(BenchmarkError, match="no peaks"):
        registry.peaks("TPU v99")
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
