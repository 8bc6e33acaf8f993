"""The flash-attention backward's count and its roofline reader."""
import json

import pytest
from chipbench_util import CHIP_DIR

from bench import trace as T
from bench.harness import Ctx
from bench.registry import Registry

RECORDED = CHIP_DIR / "testdata" / "qwen3-4b.silo.trace.json"


def test_flash_attention_bwd_counts_four_products_a_pair():
    fl = Registry().flops("flash_attention_bwd")
    # L = 4: 10 causal pairs, 8·D operations each (no recomputed q·kᵀ)
    assert fl.flops(bh=1, seq_len=4, head_dim=2) == 8 * 10 * 2
    fwd = Registry().flops("flash_attention")
    assert fl.flops(128, 1024, 128) == 2 * fwd.flops(128, 1024, 128)
    # q, dO and dq over the q heads; k, v, dk and dv over the kv heads
    assert fl.bytes_moved(bh=4, bkv=1, seq_len=8, head_dim=2,
                          itemsize=2) == 2 * 8 * 2 * (3 * 4 + 4 * 1)
    peak = Registry().peaks("TPU v5 lite")
    # silo's fp32 shapes: bound by operations, 0.35 ms
    least = fl.least_time_s(128, 32, 1024, 128, 4, peak)
    assert least == pytest.approx(fl.flops(128, 1024, 128) / 197e12)
    assert least == pytest.approx(3.49e-4, rel=1e-3)


def test_bwd_roofline_reads_nothing_without_its_kernels():
    """The recorded silo trace predates the backward kernels: their
    roofline reads nothing there, while the forward's reads."""
    tr = json.loads(RECORDED.read_text())
    tr["devices"] = {int(c): d for c, d in tr["devices"].items()}
    registry = Registry()
    ctx = Ctx(trace=tr, config=registry.config("qwen3-4b"),
              workload=registry.cell("qwen3-4b.silo")["workload"],
              peak=registry.peaks("TPU v5 lite"), chips=1,
              tokens_per_s=30000.0, registry=registry)
    assert not T.kernel_events(tr, 0, "_flash_dkv_kernel")
    assert registry.metric("flash_attention_bwd_roofline").read(ctx) is None
    assert registry.metric("flash_attention_roofline").read(ctx) > 0
