"""The round's phases: the program's scopes add no op, the compiled round
names each phase, and the reduction reads per-phase device time and idle
time inside loops from a trace (hand-made, and recorded on a TPU v5e)."""
from __future__ import annotations

import contextlib
import json
import re

import jax
import jax.numpy as jnp
import pytest
from chipbench_util import CHIP_DIR, tiny_copy

from bench import phases as P
from bench import trace as T
from bench.harness import Ctx, program_configs
from bench.registry import Registry

MS = 1_000_000      # ns
PHASE_METRICS = ("round.fwd_bwd_ms", "round.recompute_ms",
                 "round.local_update_ms", "round.client_exchange_ms",
                 "round.server_ms", "round.unscoped_ms",
                 "device.idle_in_loop_share")
BUSY_METRICS = ("round.fwd_bwd_ms", "round.local_update_ms",
                "round.client_exchange_ms", "round.server_ms",
                "round.unscoped_ms")
# ops that do no work of their own: a while's state, its loop, constants
BOOKKEEPING = ("constant", "parameter", "get-tuple-element", "tuple",
               "bitcast", "while")
_METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


def tiny_round_hlo(tmp_path, run_override=None):
    """The tiny cell's round, lowered and compiled on the CPU."""
    from repro.launch.train import make_train_step, state_shapes
    cell = tiny_copy(tmp_path).cell("tiny.cohort")
    mcfg, fed, run = program_configs(cell, "cpu", run_override)
    rnd = cell["workload"]["round"]
    tokens = jax.ShapeDtypeStruct(
        (1, rnd["clients"], rnd["local_steps"], rnd["rows"], rnd["seq_len"]),
        jnp.int32)
    step = jax.jit(make_train_step(mcfg, fed, run))
    return step.lower(state_shapes(mcfg, fed, run),
                      {"tokens": tokens, "labels": tokens}).compile().as_text()


def strip_metadata(hlo):
    """The HLO text without op metadata and the source-location tables
    (``FileNames`` ... ``StackFrames``) that precede the computations."""
    lines = hlo.splitlines()
    first = next(i for i, line in enumerate(lines[1:], 1)
                 if line.startswith(("%", "ENTRY")))
    return "\n".join([lines[0]] + [_METADATA.sub("", line)
                                   for line in lines[first:]])


def test_scopes_add_no_op(tmp_path, monkeypatch):
    scoped = tiny_round_hlo(tmp_path / "a")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = tiny_round_hlo(tmp_path / "b")
    assert "fedadc.fwd_bwd" in scoped and "fedadc." not in plain
    assert strip_metadata(scoped) == strip_metadata(plain)


def _instructions(hlo):
    """[(computation, instruction, opcode, has op_name)] and the names of
    the computations that are a ``while``'s body."""
    header = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
    opcode = re.compile(r"=\s*\S+\s+([\w\-]+)\(")
    out, bodies, comp = [], set(), None
    for line in hlo.splitlines():
        m = header.match(line)
        if m:
            comp = m.group(1)
            continue
        m = P._INSTR.match(line)
        if m and comp:
            op = opcode.search(line)
            out.append((comp, m.group(1), op.group(1) if op else "",
                        'op_name="' in line))
        bodies.update(re.findall(r"body=%([\w.\-]+)", line))
    return out, bodies


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_compiled_round_names_its_phases(tmp_path, compute_dtype):
    """The scopes survive scan, grad, remat and fusion.  In float32 the
    compiler folds the broadcast's one op (m̄ = β·m/H) into the local
    update's half-step, so that phase keeps no instruction of its own;
    the bf16 round casts θ and m there.  The client loop's bookkeeping
    (its counter, each client's slice of the tokens) and the loss mean
    carry an op_name outside every scope: they are the few working
    instructions with no phase."""
    hlo = tiny_round_hlo(tmp_path, {"compute_dtype": compute_dtype})
    phases = P.hlo_phases(hlo)
    found = {v["phase"] for v in phases.values()} - {None}
    expect = set(P.PHASES) - ({"broadcast"} if compute_dtype == "float32"
                              else set())
    assert found >= expect
    # a reduction's scalar adder keeps a relative op_name, with no scope
    recompute = {v["phase"] for v in phases.values() if v["recompute"]}
    assert "fwd_bwd" in recompute and recompute <= {"fwd_bwd", None}
    instrs, bodies = _instructions(hlo)
    assert bodies
    for _, name, op, named in instrs:
        if named and op in ("dot", "custom-call", "convolution"):
            assert phases[name]["phase"] is not None, name
    work = [name for comp, name, op, named in instrs
            if named and (op == "fusion" or comp in bodies)
            and op not in BOOKKEEPING]
    unphased = [n for n in work if phases[n]["phase"] is None]
    assert len(unphased) < 0.05 * len(work), unphased


def test_phase_of_reads_the_innermost_scope():
    assert P.phase_of("jit(f)/fedadc.local_update/fedadc.fwd_bwd/"
                      "transpose(jvp())/dot_general") == "fwd_bwd"
    assert P.phase_of("jit(f)/transpose(jvp(fedadc.fwd_bwd))/mul") == \
        "fwd_bwd"
    assert P.phase_of("jit(f)/fedadc.local_update/sub") == "local_update"
    assert P.phase_of("jit(f)/fedadc.nothing/sub") is None
    assert P.phase_of("jit(f)/while/body/add") is None
    hlo = ('  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={'
           'op_name="jit(f)/fedadc.fwd_bwd/checkpoint/rematted_computation'
           '/mul" stack_frame_id=4}\n'
           '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)\n')
    assert P.hlo_phases(hlo) == {
        "fusion.3": {"phase": "fwd_bwd", "recompute": True},
        "copy.1": {"phase": None, "recompute": False}}


def hand_made():
    """One chip, a 100 ms window holding one round: a client loop (while)
    over two clients, then the server, then idle between programs."""
    ops = [("while.1", 10 * MS, 70 * MS),
           ("fusion.fwd", 10 * MS, 30 * MS),      # fwd_bwd, 5 ms recompute
           ("fusion.remat", 30 * MS, 35 * MS),
           ("fusion.sgd", 35 * MS, 40 * MS),      # local_update
           ("fusion.delta", 44 * MS, 46 * MS),    # uplink; idle 40..44
           ("fusion.acc", 46 * MS, 50 * MS),      # accumulate
           ("fusion.fwd", 50 * MS, 65 * MS),
           ("copy.7", 65 * MS, 67 * MS),          # compiler's copy
           ("fusion.sgd", 67 * MS, 70 * MS),
           ("fusion.mean", 72 * MS, 75 * MS),     # aggregate; idle 70..72
           ("fusion.server", 75 * MS, 80 * MS)]   # server_update
    phases = {"fusion.fwd": {"phase": "fwd_bwd", "recompute": False},
              "fusion.remat": {"phase": "fwd_bwd", "recompute": True},
              "fusion.sgd": {"phase": "local_update", "recompute": False},
              "fusion.delta": {"phase": "uplink", "recompute": False},
              "fusion.acc": {"phase": "accumulate", "recompute": False},
              "fusion.mean": {"phase": "aggregate", "recompute": False},
              "fusion.server": {"phase": "server_update",
                                "recompute": False},
              "while.1": {"phase": None, "recompute": False},
              "copy.7": {"phase": None, "recompute": False}}
    return {"window": [0, 100 * MS], "host": [],
            "devices": {0: {"ops": [list(o) for o in ops], "async": []}},
            "kernels": {}, "phases": phases}


def test_phase_busy_idle_in_loop_and_gap_phases():
    tr = hand_made()
    assert P.phase_busy_s(tr, 0, {"fwd_bwd"}) == pytest.approx(0.040)
    assert P.phase_busy_s(tr, 0, {"fwd_bwd"}, recompute=True) == \
        pytest.approx(0.005)
    assert P.phase_busy_s(tr, 0, {"local_update"}) == pytest.approx(0.008)
    assert P.phase_busy_s(tr, 0, {"uplink", "accumulate"}) == \
        pytest.approx(0.006)
    # the while holds its body's ops, so it is no phase's busy time
    assert P.phase_busy_s(tr, 0, {None}) == pytest.approx(0.002)
    # idle 0..10, 40..44, 70..72, 80..100: only 40..44 is inside the loop
    assert T.busy_s(tr, 0) == pytest.approx(0.064)
    assert P.idle_in_loop_s(tr, 0) == pytest.approx(0.004)
    gaps = P.gap_phases(tr, 0, top=3)
    assert gaps == [["server_update→window", pytest.approx(0.020)],
                    ["window→fwd_bwd", pytest.approx(0.010)],
                    ["local_update→uplink", pytest.approx(0.004)]]
    assert P.top_ops(tr, 0, "fwd_bwd") == [["fusion.fwd",
                                            pytest.approx(0.035)],
                                           ["fusion.remat",
                                            pytest.approx(0.005)]]
    assert P.top_ops(tr, 0, None) == [["copy.7", pytest.approx(0.002)]]


def test_an_op_holding_a_zero_length_event_reads_as_idle():
    tr = hand_made()
    # the profiler stamps a custom-call of zero length at an op's start:
    # the op then holds it, and its 3 ms read as idle inside the loop
    tr["devices"][0]["ops"] += [["custom-call.1", 67 * MS, 67 * MS],
                                ["copy-done.3", 72 * MS - 2, 72 * MS]]
    assert T.busy_s(tr, 0) == pytest.approx(0.061)
    assert P.idle_in_loop_s(tr, 0) == pytest.approx(0.007)
    assert P.zero_length_holders(tr, 0) == [("fusion.sgd", 67 * MS,
                                             70 * MS)]
    assert P.zero_length_holders(hand_made(), 0) == []
    # the gap the held op leaves is named by the ops around it that work
    assert ["unscoped→aggregate", pytest.approx(0.005)] in \
        P.gap_phases(tr, 0)


def ctx_of(tr, tokens_per_s, cell="qwen3-4b.cohort"):
    registry = Registry()
    return Ctx(trace=tr, config=registry.config("qwen3-4b"),
               workload=registry.cell(cell)["workload"],
               peak=registry.peaks("TPU v5 lite"), chips=1,
               tokens_per_s=tokens_per_s, registry=registry)


def read_all(ctx):
    registry = Registry()
    return {name: registry.metric(name).read(ctx) for name in PHASE_METRICS}


def test_busy_metrics_add_up_to_busy_per_round():
    tr = hand_made()
    # the window holds half a cohort round (32,768 tokens)
    ctx = ctx_of(tr, tokens_per_s=0.5 * 32768 / 0.1)
    got = read_all(ctx)
    assert got["round.fwd_bwd_ms"] == pytest.approx(80.0)
    assert got["round.recompute_ms"] == pytest.approx(10.0)
    assert got["round.server_ms"] == pytest.approx(16.0)
    assert got["device.idle_in_loop_share"] == pytest.approx(4.0)
    busy_ms = 1e3 * T.busy_s(tr, 0) / 0.5
    assert sum(got[m] for m in BUSY_METRICS) == pytest.approx(busy_ms)


def test_phase_metrics_read_nothing_without_phases():
    tr = hand_made()
    del tr["phases"]
    assert all(v is None for v in read_all(ctx_of(tr, 1e4)).values())
    # a program without the scopes: every op has no phase
    tr = hand_made()
    tr["phases"] = {op: {"phase": None, "recompute": False}
                    for op in tr["phases"]}
    assert all(v is None for v in read_all(ctx_of(tr, 1e4)).values())


RECORDED = CHIP_DIR / "testdata" / "qwen3-4b.cohort.phases.trace.json"
RECORDED_OLD = CHIP_DIR / "testdata" / "qwen3-4b.silo.trace.json"


def load(path):
    tr = json.loads(path.read_text())
    tr["devices"] = {int(c): d for c, d in tr["devices"].items()}
    return tr


def test_recorded_trace_reads_every_phase_metric():
    tr = load(RECORDED)
    ctx = ctx_of(tr, tr["tokens_per_s"])
    got = read_all(ctx)
    round_ms = 1e3 * T.window_s(tr) / P.rounds_in_window(ctx)
    for name, value in got.items():
        limit = 100.0 if name.endswith("share") else round_ms
        assert value is not None and 0 <= value <= limit, (name, value)
    assert got["round.fwd_bwd_ms"] > 0 and got["round.local_update_ms"] > 0
    busy_ms = 1e3 * T.busy_s(tr, 0) / P.rounds_in_window(ctx)
    assert sum(got[m] for m in BUSY_METRICS) == pytest.approx(busy_ms,
                                                              rel=1e-9)
    idle_share = 100.0 * (1 - T.busy_s(tr, 0) / T.window_s(tr))
    assert got["device.idle_in_loop_share"] <= idle_share + 1e-9
    assert P.gap_phases(tr, 0, top=10)
    # the old recorded trace carries no phases: every phase metric is None
    old = load(RECORDED_OLD)
    assert all(v is None for v in read_all(ctx_of(old, 3e4)).values())
