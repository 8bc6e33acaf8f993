"""Fused delta-compression kernels (the uplink hot spot).

Both compressors are quantize-and-decompress round trips: the engines
transport the *decompressed* lossy delta (what the server would reconstruct
from the wire) and keep the residual as the client's error-feedback memory.
Unfused, XLA materialises the intermediate quantised tensor and the
(v − q) subtraction in HBM; these kernels emit the reconstruction AND the
residual from a single VMEM pass over the input — one read, two writes,
no intermediates.

* ``qsgd_2d`` — QSGD-style stochastic uniform quantisation: magnitudes are
  scaled into ``s`` levels, stochastically rounded (the uniform draw arrives
  as an operand so CPU-interpret and TPU runs are bit-identical to the ref),
  then dequantised in-register.
* ``threshold_select_2d`` — top-k as a per-block threshold select: the k-th
  largest magnitude is computed once per leaf upstream (``lax.top_k``); each
  block then keeps values with ``|v| ≥ τ`` and zeroes the rest, so the kernel
  itself stays a streaming elementwise pass regardless of k.

Tiling mirrors fedadc_update.py: flattened (rows, 128) lane-aligned tiles
(padding handled by the ops.py wrapper); per-leaf scalars (scale, threshold)
are broadcast along lanes like the weights in weighted_reduce.py.  They
travel in fp32 whatever the operand dtype — Mosaic extracts only 32-bit
scalars from a vector — and are read as (1, LANE) rows, not scalars.  A
scalar of the operand dtype is exact in fp32, so the threshold compare
stays exact.  The quantiser computes in fp32 (the v5e has no bf16 vector
compare), rounding each step to the operand dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 512          # 512×128 fp32 = 256 KiB per operand in VMEM


def _qsgd_kernel(v_ref, u_ref, scale_ref, q_ref, r_ref, *, s):
    # y = |v|·s/scale ; level = ⌊y⌋ + 1[u < frac(y)] ; q = sign(v)·level·scale/s
    # Each step runs in fp32 (the chip has no bf16 vector compare) and is
    # rounded to the operand dtype, as the oracle's in-dtype arithmetic
    # rounds it — so the level decisions match the oracle's.
    dt = v_ref.dtype

    def rd(x):
        return x.astype(dt).astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    scale = scale_ref[...]                          # (1, LANE) fp32, exact
    inv = rd(jnp.where(scale > 0, rd(s / jnp.maximum(scale, 1e-30)), 0.0))
    y = rd(jnp.abs(v) * inv)
    lower = jnp.floor(y)
    level = rd(lower + (u < rd(y - lower)).astype(jnp.float32))
    q = rd(rd(jnp.sign(v) * level) * rd(scale / s))
    q_ref[...] = q.astype(dt)
    r_ref[...] = (v - q).astype(dt)


def _threshold_kernel(v_ref, t_ref, q_ref, r_ref):
    # q = v·1[|v| ≥ τ] ; r = v − q   (τ = per-leaf k-th largest magnitude)
    v = v_ref[...]
    keep = jnp.abs(v).astype(jnp.float32) >= t_ref[...]
    q = jnp.where(keep, v, jnp.zeros_like(v))
    q_ref[...] = q
    r_ref[...] = v - q


def _tiled_call(kernel, arrays, scalars, interpret, **kw):
    """arrays: (rows, LANE) operands; scalars: per-leaf values broadcast to
    (1, LANE) and replicated to every block.  -> (q, residual)."""
    rows = arrays[0].shape[0]
    dtype = arrays[0].dtype
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block),)
    spec = pl.BlockSpec((block, LANE), lambda i: (i, 0))
    sspec = pl.BlockSpec((1, LANE), lambda i: (0, 0))
    s2d = [jnp.broadcast_to(jnp.asarray(s, dtype).astype(jnp.float32)
                            .reshape(1, 1), (1, LANE))
           for s in scalars]
    out_shape = [jax.ShapeDtypeStruct(arrays[0].shape, dtype)] * 2
    return pl.pallas_call(
        functools.partial(kernel, **kw),
        grid=grid,
        in_specs=[spec] * len(arrays) + [sspec] * len(s2d),
        out_specs=[spec, spec],
        out_shape=out_shape,
        interpret=interpret,
    )(*arrays, *s2d)


def qsgd_2d(v, u, scale, s, interpret=False):
    """v, u (rows, LANE); scale scalar -> (dequantised q, residual v − q)."""
    return _tiled_call(_qsgd_kernel, [v, u], [scale], interpret, s=float(s))


def threshold_select_2d(v, thresh, interpret=False):
    """v (rows, LANE); thresh scalar -> (selected q, residual v − q)."""
    return _tiled_call(_threshold_kernel, [v], [thresh], interpret)
