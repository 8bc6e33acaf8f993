"""Shared helpers of the benchmark's CPU tests: a tiny Qwen3 configuration
and cell, written as new files into a temporary copy of the benchmark's
directory, and a run of the harness on them without a chip."""
from __future__ import annotations

import copy
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CHIP_DIR = REPO / "benchmarks" / "chip"
for p in (str(REPO / "src"), str(CHIP_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny", "source": "a tiny Qwen3 for tests",
    "reference": "qwen3", "model_type": "qwen3",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True,
    "mesh": {"data": 1, "model": 1},
    "program": {"ModelConfig": {
        "arch_id": "tiny", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "qk_norm": True, "rope_theta": 1000000.0, "norm_eps": 1e-6,
        "tie_embeddings": True}},
}

# Set from the tiny cell's readings on the CPU (float32 throughout): sound
# runs read under 1e-5 on every number; the bfloat16 control reads 1e-2 or
# more on both leaf numbers, and every planted fault more than that.
TINY_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
               "update_norm_gap": 1e-3, "grad_leaf_gap": 1e-3}

TINY_WORKLOAD = {
    "name": "tiny.cohort", "config": "tiny", "traffic": "cohort",
    "chips": 1, "why": "a tiny cell for tests",
    "round": {"clients": 4, "local_steps": 2, "rows": 2, "seq_len": 16},
    "fed": {"strategy": "fedadc", "variant": "nesterov", "eta": 0.05,
            "alpha": 1.0, "beta_global": 0.8, "beta_local": 0.8},
    "run": {"param_dtype": "float32", "compute_dtype": "float32",
            "remat": "full"},
    "control": {"run": {"compute_dtype": "bfloat16"}},
    "limits": TINY_LIMITS,
}


def tiny_copy(tmp: Path, config=None, workload=None):
    """A copy of the benchmark's directory under `tmp`, with the tiny
    configuration and cell added as new files and a BENCHMARK.json that
    lists the cell.  -> the copy's Registry."""
    from bench.registry import Registry
    config = copy.deepcopy(config or TINY_CONFIG)
    workload = copy.deepcopy(workload or TINY_WORKLOAD)
    chip = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP_DIR, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (chip / "workloads" / f"{workload['name']}.json").write_text(
        json.dumps(workload))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"].append({k: workload[k] for k in
                               ("name", "config", "traffic", "chips",
                                "why")})
    for m in bench["per_layer"]:
        m.get("workloads", []).append(workload["name"])
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return Registry(chip, tmp / "BENCHMARK.json")


def run_tiny(registry, name="tiny.cohort", seed=7, wrap_step=None):
    """One run of a tiny cell on the CPU, the look for a chip skipped."""
    from bench.harness import run_cell
    return run_cell(registry, name, seed, 0.05, False, time.perf_counter(),
                    require_tpu=False, wrap_step=wrap_step)
