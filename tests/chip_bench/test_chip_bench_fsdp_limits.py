"""The four-chip cell ``qwen3-14b.silo-fsdp2x2`` under its own limits.

A tiny Qwen3 with an untied head, its state FSDP x TP over (data, model) =
(2, 2) on four virtual CPU devices, runs through the harness with the
FedADC settings and the limits of ``workloads/qwen3-14b.silo-fsdp2x2.json``:
a sound run is correct, and a round that leaves out half its clients or
half of each step's rows is not."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest
from chipbench_util import CHIP_DIR, REPO, TINY_CONFIG, TINY_WORKLOAD

from bench import compare as C
from bench.registry import Registry

CELL = "qwen3-14b.silo-fsdp2x2"
WORKLOAD = json.loads((CHIP_DIR / "workloads" / f"{CELL}.json").read_text())
MESH = {"data": 2, "model": 2}

MESH_RUN = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {tests!r})
import chipbench_util as U
import test_chip_bench_correct as T
reg = U.tiny_copy(Path(tempfile.mkdtemp()), config=json.loads(sys.argv[1]),
                  workload=json.loads(sys.argv[2]))
out = {{}}
for name, wrap in (("sound", None), ("half_rows", T.half_rows),
                   ("half_clients", T.half_clients)):
    out[name] = U.run_tiny(reg, name="tiny.fsdp", wrap_step=wrap)["checks"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_checks():
    """Each run's checks: the sound round and the two planted faults."""
    config = dict(TINY_CONFIG, mesh=MESH, tie_word_embeddings=False,
                  program={"ModelConfig": dict(
                      TINY_CONFIG["program"]["ModelConfig"],
                      tie_embeddings=False)})
    workload = dict(TINY_WORKLOAD, name="tiny.fsdp", traffic="fsdp",
                    chips=WORKLOAD["chips"],
                    round=dict(TINY_WORKLOAD["round"],
                               rows=WORKLOAD["round"]["rows"]),
                    fed=WORKLOAD["fed"], run=WORKLOAD["run"],
                    limits=WORKLOAD["limits"])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-c",
         MESH_RUN.format(tests=str(REPO / "tests" / "chip_bench")),
         json.dumps(config), json.dumps(workload)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("run, correct", [("sound", True),
                                          ("half_rows", False),
                                          ("half_clients", False)])
def test_fsdp_cell_limits_pass_sound_and_fail_faults(mesh_checks, run,
                                                     correct):
    checks = mesh_checks[run]
    assert {n: c["limit"] for n, c in checks.items()} == WORKLOAD["limits"]
    assert C.passed(checks) == correct, checks


@pytest.mark.parametrize("key, value", [
    ("chips", 4), ("mesh", MESH), ("tie_word_embeddings", False),
    ("rows_per_data_shard", 2)])
def test_registry_loads_the_fsdp_cell(key, value):
    cell = Registry().cell(CELL)
    config = cell["config_file"]
    got = {"chips": cell["chips"], "mesh": config["mesh"],
           "tie_word_embeddings": config["tie_word_embeddings"],
           "rows_per_data_shard": cell["workload"]["round"]["rows"]
           // config["mesh"]["data"]}
    assert got[key] == value
