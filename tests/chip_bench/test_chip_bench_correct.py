"""The correctness check of a run: sound runs pass, and the control (the
program's own bfloat16 path) and every fault planted under the timed path
fail.  A tiny cell, run through the harness on the CPU with the look for a
chip skipped."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest
from chipbench_util import REPO, TINY_CONFIG, TINY_WORKLOAD, run_tiny, \
    tiny_copy

from bench import compare as C
from bench.harness import Cell, first_rounds


def unchanged(step, cell):
    """A round that returns the state it was given."""
    return lambda state, batch: (state, step(state, batch)[1])


def half_clients(step, cell):
    """A round that leaves out half its clients and averages the rest."""
    return lambda state, batch: step(state, {
        k: v[:, :v.shape[1] // 2] for k, v in batch.items()})


def half_rows(step, cell):
    """A round whose local steps see the first half of their rows only,
    as a data-parallel step that skips the exchange between shards."""
    return lambda state, batch: step(state, {
        k: v[:, :, :, :v.shape[3] // 2] for k, v in batch.items()})


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct(registry):
    result = run_tiny(registry)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [unchanged, half_clients, half_rows])
def test_planted_fault_is_not_correct(registry, fault):
    result = run_tiny(registry, wrap_step=fault)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_control_is_not_correct(registry, seed):
    """The program with its bfloat16 compute path on, in the place of the
    float32 one the cell states."""
    cell = Cell(registry, "tiny.cohort", jax.devices(), "cpu",
                run_override=TINY_WORKLOAD["control"]["run"])
    compiled, state, key = cell.build(seed)
    _, control, _ = first_rounds(cell, compiled, state, key, seed)
    checks = C.compare(control, cell.reference(seed),
                       TINY_WORKLOAD["limits"])
    assert not C.passed(checks), checks


MESH_RUN = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {tests!r})
import chipbench_util as U
import test_chip_bench_correct as T
reg = U.tiny_copy(Path(tempfile.mkdtemp()), config=json.loads(sys.argv[1]),
                  workload=json.loads(sys.argv[2]))
out = {{}}
for name, wrap in (("sound", None), ("no_exchange", T.half_rows)):
    out[name] = U.run_tiny(reg, name="tiny.mesh", wrap_step=wrap)["correct"]
print(json.dumps(out))
"""


def test_sharded_cell_without_exchange_is_not_correct():
    """On four virtual CPU devices, a (data, model) = (2, 2) cell passes,
    and fails when each step keeps the first data shard's rows only."""
    config = dict(TINY_CONFIG, mesh={"data": 2, "model": 2})
    workload = dict(TINY_WORKLOAD, name="tiny.mesh", traffic="mesh",
                    chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-c",
         MESH_RUN.format(tests=str(REPO / "tests" / "chip_bench")),
         json.dumps(config), json.dumps(workload)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}
