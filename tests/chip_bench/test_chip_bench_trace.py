"""The trace reduction, the operation and byte counts and the peak table,
on hand-made traces and on a slice of a trace recorded on a TPU v5e."""
from __future__ import annotations

import base64
import json

import pytest
from chipbench_util import CHIP_DIR

from bench import trace as T
from bench.harness import Ctx
from bench.registry import Registry

MS = 1_000_000      # ns


def trace_of(ops, window=(0, 100 * MS), async_ops=(), kernels=None,
             host=()):
    return {"window": list(window), "host": [list(h) for h in host],
            "devices": {0: {"ops": [list(o) for o in ops],
                            "async": [list(o) for o in async_ops]}},
            "kernels": kernels or {}}


def test_union_clip_and_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert T.clip([(0, 5), (8, 12), (20, 30)], 2, 10) == [(2, 5), (8, 10)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]


def test_busy_and_idle_count_leaf_ops_only():
    # a while loop spans 10..90 ms but its body leaves a gap at 40..60
    ops = [("while.1", 10 * MS, 90 * MS), ("fusion.1", 10 * MS, 40 * MS),
           ("fusion.2", 60 * MS, 90 * MS), ("copy.1", 95 * MS, 120 * MS)]
    tr = trace_of(ops)
    assert [o[0] for o in T.leaves(tr["devices"][0]["ops"])] == \
        ["fusion.1", "fusion.2", "copy.1"]
    assert T.busy_s(tr, 0) == pytest.approx(0.065)
    assert T.window_s(tr) == pytest.approx(0.1)
    assert T.idle_gaps(tr, 0) == [(0, 10 * MS), (40 * MS, 60 * MS),
                                  (90 * MS, 95 * MS)]
    self = T.self_times(tr, 0)
    assert self["while.1"] == pytest.approx(0.02)
    assert self["fusion.1"] == pytest.approx(0.03)


def test_kernel_time_and_exposed_collectives():
    kernels = {"closed_call.3": {"kernel": "_axpy_kernel",
                                 "result": ["bf16", [1024, 128]],
                                 "operands": [["bf16", [1024, 128]]] * 2}}
    ops = [("closed_call.3", 0, 10 * MS), ("closed_call.3", 20 * MS, 25 * MS),
           ("fusion.9", 30 * MS, 50 * MS),
           ("all-gather-done.2", 50 * MS, 55 * MS),
           ("all-reduce.4", 70 * MS, 80 * MS)]
    # an async all-gather from 40 to 55 ms: hidden behind fusion.9 until
    # 50 ms, then the core waits on its done
    tr = trace_of(ops, async_ops=[("all-gather-start.2", 40 * MS, 55 * MS),
                                  ("copy-start.1", 0, 90 * MS)],
                  kernels=kernels)
    ev = T.kernel_events(tr, 0, "_axpy_kernel")
    assert sum(e - s for _, s, e in ev) == 15 * MS
    assert T.exposed_collective_s(tr, 0) == pytest.approx(0.015)
    assert T.kernel_events(tr, 0, "_flash_kernel") == []


def test_hlo_kernels_reads_name_and_shapes():
    body = base64.b64encode(b"\x00\x01func_flash_kernel\x00tail").decode()
    hlo = ('  %closed_call.7 = bf16[128,1024,128]{2,1,0} custom-call('
           '%a, %b, %c), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={bf16[128,1024,128]{2,1,0}, '
           'bf16[32,1024,128]{2,1,0}, bf16[32,1024,128]{2,1,0}}, '
           'backend_config={"custom_call_config":{"body":"' + body + '"}}\n'
           '  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop\n')
    k = T.hlo_kernels(hlo)
    assert list(k) == ["closed_call.7"]
    assert k["closed_call.7"]["kernel"] == "func_flash_kernel"
    assert k["closed_call.7"]["result"] == ["bf16", [128, 1024, 128]]
    assert k["closed_call.7"]["operands"][1] == ["bf16", [32, 1024, 128]]
    assert T.op_name("%fusion.12 = bf16[2] fusion(%p)") == "fusion.12"


def test_causal_flash_attention_counts_pairs_once():
    fl = Registry().flops("flash_attention")
    # L = 4: 10 causal (query, key) pairs, not 16
    assert fl.flops(bh=1, seq_len=4, head_dim=2) == 4 * 10 * 2
    # at L = 1024 the causal count is just over half the full square
    full = 4 * 1024 * 1024 * 128
    assert fl.flops(1, 1024, 128) / full == pytest.approx(0.5, rel=1e-3)
    assert fl.bytes_moved(bh=4, bkv=1, seq_len=8, head_dim=2,
                          itemsize=2) == 2 * 8 * 2 * (2 * 4 + 2 * 1)
    peak = Registry().peaks("TPU v5 lite")
    assert fl.least_time_s(128, 32, 1024, 128, 2, peak) == pytest.approx(
        fl.flops(128, 1024, 128) / 197e12)


def test_fused_axpy_is_bound_by_memory():
    fl = Registry().flops("fused_axpy")
    peak = Registry().peaks("TPU v5 lite")
    assert fl.least_time_s(1 << 20, 2, peak) == pytest.approx(
        3 * 2 * (1 << 20) / 819e9)


def test_round_flops_per_token():
    fl = Registry().flops("round")
    cfg = json.loads((CHIP_DIR / "configs" / "qwen3-4b.json").read_text())
    assert fl.matmul_params(cfg) == 386_662_400
    attn = 3 * 12 * 32 * 128 * (1024 + 1) / 2
    assert fl.flops_per_token(cfg, 1024) == 6 * 386_662_400 + attn


RECORDED = CHIP_DIR / "testdata" / "qwen3-4b.silo.trace.json"


def recorded():
    tr = json.loads(RECORDED.read_text())
    tr["devices"] = {int(c): d for c, d in tr["devices"].items()}
    return tr


def test_recorded_trace_reduces():
    tr = recorded()
    busy, span = T.mean_busy_s(tr), T.window_s(tr)
    assert 0 < busy <= span
    flash = T.kernel_events(tr, 0, "_flash_kernel")
    axpy = T.kernel_events(tr, 0, "_axpy_kernel")
    assert flash and axpy
    b = T.breakdown(tr)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert sum(t for _, t in b["device_ops"]) <= span
    registry = Registry()
    ctx = Ctx(trace=tr, config=registry.config("qwen3-4b"),
              workload=registry.cell("qwen3-4b.silo")["workload"],
              peak=registry.peaks("TPU v5 lite"), chips=1,
              tokens_per_s=30000.0, registry=registry)
    readers = {m["name"]: m["reader"]
               for m in registry.per_layer("qwen3-4b.silo")}
    for name in ("flash_attention_roofline", "fused_axpy_roofline",
                 "device.idle_share", "round.mfu"):
        value = readers[name].read(ctx)
        assert 0 < value < 100, (name, value)
    # one chip: no collective ran, so the metric reads nothing
    assert "collective.exposed_share" not in readers
    assert registry.metric("collective.exposed_share").read(ctx) is None
