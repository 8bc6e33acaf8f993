"""Compile the round's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at the widths the chip smoke test
uses (qwen3-4b: d_model 2560, d_ff 9728, 32 q / 8 kv heads of 128) and
compiles it with the TPU compiler for one chip of a described ``v5e:2x2``
topology.  A kernel the chip's compiler refuses (a block shape off the
8 × 128 tiling, a scalar it cannot extract, too much VMEM) fails here,
at no chip time.  Each test asserts the program holds the Mosaic kernel
(``tpu_custom_call``), so a silent fallback to XLA fails too.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import os
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compress as _cp
from repro.kernels import fedadc_update as _fu
from repro.kernels import ops
from repro.kernels import weighted_reduce as _wr

CHIP_DIR = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
D_MODEL, D_FF = 2560, 9728
ROWS = D_MODEL * D_FF // ops.LANE          # the MLP leaf as (rows, 128) tiles
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The ops.py wrappers pick interpret mode off the TPU; these compiles
    target the TPU, so the wrappers must emit the Mosaic kernel."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", DTYPES)
def test_fedadc_update_compiles(one_chip, dtype):
    leaf = _sds((ROWS, ops.LANE), dtype, one_chip)
    _compile(lambda x, y: _fu.fused_axpy(x, y, -0.05), leaf, leaf)


def test_fused_axpy_keeps_leaf_layout(one_chip, mosaic):
    """The local SGD step on the stacked MLP leaf as the round holds it:
    the kernel takes the leaf in its own (8, 128)-tiled layout, so no
    copy or reshape of the leaf's size sits on either side of it."""
    shape = (3, D_MODEL, D_FF)
    leaf = _sds(shape, jnp.float32, one_chip)
    hlo = _compile(lambda x, y: ops.fused_axpy(x, y, -0.05), leaf,
                   leaf).as_text()
    n = math.prod(shape)
    relayouts = [
        name for name, dims in re.findall(
            r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo)
        if ("copy" in name or "reshape" in name)
        and math.prod(int(d) for d in dims.split(",") if d) == n]
    assert not relayouts, relayouts


@pytest.mark.parametrize("dtype", DTYPES)
def test_weighted_reduce_compiles(one_chip, dtype):
    _compile(_wr.weighted_reduce_2d,
             _sds((4, ROWS, ops.LANE), dtype, one_chip),
             _sds((4,), jnp.float32, one_chip))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["threshold", "qsgd"])
def test_compress_compiles(one_chip, kernel, dtype):
    leaf = _sds((ROWS, ops.LANE), dtype, one_chip)
    scalar = _sds((), dtype, one_chip)
    if kernel == "threshold":
        _compile(_cp.threshold_select_2d, leaf, scalar)
    else:
        _compile(lambda v, u, s: _cp.qsgd_2d(v, u, s, 255), leaf, leaf,
                 scalar)


def _qkv(one_chip, L=2048):
    return (_sds((1, L, 32, 128), jnp.bfloat16, one_chip),
            _sds((1, L, 8, 128), jnp.bfloat16, one_chip),
            _sds((1, L, 8, 128), jnp.bfloat16, one_chip))


def test_flash_attention_forward_compiles(one_chip, mosaic):
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
             *_qkv(one_chip))


def _fwd_bwd(q, k, v, g):
    out, pullback = jax.vjp(
        lambda *a: ops.flash_attention(*a, causal=True), q, k, v)
    return out, pullback(g)


def test_flash_attention_backward_compiles(one_chip, mosaic):
    """The custom VJP as a training step runs it: the kernel forward and
    the Pallas dq and dk/dv kernels of the backward in one program."""
    q, k, v = _qkv(one_chip)
    compiled = _compile(_fwd_bwd, q, k, v, q)
    out, grads = compiled.out_info
    assert out.shape == q.shape
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


# The benchmark cells' attention as each device sees it, fp32, head dim
# 128: (batch, L, q heads, kv heads).  The 14B shard is one device's part
# of the (2, 2) mesh: half the batch over "data", half the heads over
# "model", so a GQA group of 5.
CELL_ATTENTION = {
    "qwen3-4b.silo": (4, 1024, 32, 8),
    "qwen3-4b.cohort": (1, 512, 32, 8),
    "qwen3-14b.shard": (2, 1024, 20, 4),
}


def _cell_qkv(one_chip, cell):
    B, L, H, Hk = CELL_ATTENTION[cell]
    return (_sds((B, L, H, 128), jnp.float32, one_chip),
            _sds((B, L, Hk, 128), jnp.float32, one_chip),
            _sds((B, L, Hk, 128), jnp.float32, one_chip))


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_attention_cell_shapes_compile(one_chip, mosaic, cell):
    """At each cell's shapes the kernels' own block choices fit the
    chip's VMEM, forward and under the VJP a training step takes.  The
    training step's attention is the three kernels and nothing of the
    dense oracle: no float32 (…, L, L) array is left in its program."""
    q, k, v = _cell_qkv(one_chip, cell)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
             q, k, v)
    compiled = _compile(_fwd_bwd, q, k, v, q)
    out, grads = compiled.out_info
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    hlo = compiled.as_text()
    sys.path.insert(0, str(CHIP_DIR))
    from bench.trace import hlo_kernels
    assert sorted(k["kernel"] for k in hlo_kernels(hlo).values()) == [
        "_flash_dkv_kernel", "_flash_dq_kernel", "_flash_kernel"]
    L = q.shape[1]
    assert not re.search(rf"f32\[(?:\d+,)*{L},{L}\]", hlo)


@pytest.fixture
def bench(monkeypatch):
    """The benchmark's own trace reader and flash-attention reader."""
    monkeypatch.syspath_prepend(str(CHIP_DIR))
    from bench import trace
    from bench.registry import Registry
    registry = Registry(CHIP_DIR)
    return (trace, registry.flops("flash_attention"),
            registry.metric("flash_attention_roofline"),
            registry.peaks("TPU v5 lite"))


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_attention_meets_its_reader(one_chip, mosaic, bench, cell):
    """`flash_attention_roofline` finds the kernel's calls by the name
    ``_flash_kernel`` and counts from its first two operands, which it
    takes to be q (B·H, L, D) and k (B·Hk, L, D).  A call that lasts its
    least time at the true shapes must read 100%."""
    trace, fl, reader, peak = bench
    B, L, H, Hk = CELL_ATTENTION[cell]
    hlo = _compile(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
                   *_cell_qkv(one_chip, cell)).as_text()
    kernels = trace.hlo_kernels(hlo)
    assert [k["kernel"] for k in kernels.values()] == ["_flash_kernel"]
    (op, info), = kernels.items()
    assert info["operands"][:2] == [["f32", [B * H, L, 128]],
                                    ["f32", [B * Hk, L, 128]]]
    least = fl.least_time_s(B * H, B * Hk, L, 128, 4, peak)
    ns = round(least * 1e9)
    tr = {"window": [0, 2 * ns], "host": [], "kernels": kernels,
          "devices": {0: {"ops": [[op, 0, ns]], "async": []}}}
    ctx = types.SimpleNamespace(trace=tr, peak=peak, flops=lambda _: fl)
    assert reader.read(ctx) == pytest.approx(100.0, rel=1e-5)


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_attention_bwd_meets_its_reader(one_chip, mosaic, bench, cell):
    """`flash_attention_bwd_roofline` finds the backward's calls by the
    names ``_flash_dkv_kernel`` and ``_flash_dq_kernel``, and counts one
    backward a dkv call from its first two operands, which it takes to
    be q (B·H, L, D) and k (B·Hk, L, D).  A call pair that lasts its
    least time at the true shapes must read 100%."""
    trace, _, _, peak = bench
    from bench.registry import Registry
    registry = Registry(CHIP_DIR)
    fl = registry.flops("flash_attention_bwd")
    reader = registry.metric("flash_attention_bwd_roofline")
    B, L, H, Hk = CELL_ATTENTION[cell]
    q, k, v = _cell_qkv(one_chip, cell)
    kernels = trace.hlo_kernels(_compile(_fwd_bwd, q, k, v, q).as_text())
    op = {info["kernel"]: name for name, info in kernels.items()}
    assert kernels[op["_flash_dkv_kernel"]]["operands"][:2] == [
        ["f32", [B * H, L, 128]], ["f32", [B * Hk, L, 128]]]
    least = fl.least_time_s(B * H, B * Hk, L, 128, 4, peak)
    ns = round(least * 1e9)
    ops_ = [[op["_flash_dkv_kernel"], 0, ns // 3],
            [op["_flash_dq_kernel"], ns // 3, ns]]
    tr = {"window": [0, 2 * ns], "host": [], "kernels": kernels,
          "devices": {0: {"ops": ops_, "async": []}}}
    ctx = types.SimpleNamespace(trace=tr, peak=peak, flops=lambda _: fl)
    assert reader.read(ctx) == pytest.approx(100.0, rel=1e-5)


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic: Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: scatter-add")
def test_sparse_reduce_compiles(one_chip, mosaic):
    """K=4 clients' top-1% wires of the MLP leaf, segment-summed."""
    n = D_MODEL * D_FF
    k = int(np.ceil(0.01 * n))
    _compile(lambda v, i, w: ops.sparse_weighted_delta_reduce(
                 v, i, w, (D_MODEL, D_FF), jnp.bfloat16),
             _sds((4, k), jnp.bfloat16, one_chip),
             _sds((4, k), jnp.int32, one_chip),
             _sds((4,), jnp.float32, one_chip))
