"""Fused FedADC update kernels (the paper's per-step hot spot).

Each kernel is one VMEM-resident pass over its operands: arithmetic
intensity is tiny (<1 flop/byte), so all they save is HBM traffic.

* ``fused_axpy`` — the local SGD step θ_half − η·g (two reads, one
  write) that every strategy's ``_sgd_step`` runs under ``use_pallas``.
  It runs on each leaf in the leaf's own shape and layout: the block
  tiles the last two dimensions, the grid runs over the leading ones
  one index at a time, and Pallas masks the edge blocks.  So a stacked
  leaf such as ``(layers, d_model, d_ff)`` reaches the kernel with no
  re-layout copy on the TPU, where an array is stored in (8, 128) tiles
  of its last two dimensions.
* ``local_update_2d`` — θ − η·(g + m̄) in one pass, where unfused XLA
  would write g + m̄ to HBM (about a third fewer bytes than two axpys).
* ``server_update_2d`` — (θ', m') from θ, m and Δ̄ in one pass.

The last two take flattened (rows, 128) tiles: their ops.py wrappers pad
each leaf to a lane-aligned size, so they only ever see hardware-aligned
blocks (8×128 float32 VREG tiles on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 512          # 512×128 fp32 = 256 KiB per operand in VMEM
AXPY_BLOCK_COLS = 1024    # the axpy block's last dim: 8 lane tiles
AXPY_BLOCK_ELEMS = 512 * AXPY_BLOCK_COLS   # 2 MiB of fp32 per operand


def _axpy_kernel(x_ref, y_ref, o_ref, *, a):
    o_ref[...] = x_ref[...] + a * y_ref[...]


def _local_update_kernel(theta_ref, g_ref, mbar_ref, o_ref, *, eta):
    # θ' = θ − η·(g + m̄)   — one pass, no HBM intermediate
    o_ref[...] = theta_ref[...] - eta * (g_ref[...] + mbar_ref[...])


def _server_update_kernel(theta_ref, m_ref, delta_ref, theta_o, m_o, *,
                          gamma, alpha_eta):
    # m' = Δ̄ + γ·m ; θ' = θ − αη·m'
    m_new = delta_ref[...] + gamma * m_ref[...]
    m_o[...] = m_new
    theta_o[...] = theta_ref[...] - alpha_eta * m_new


def _tiled_call(kernel, arrays, n_out, interpret, **kw):
    """arrays: same-shape 2D (rows, LANE) operands."""
    rows = arrays[0].shape[0]
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block),)
    spec = pl.BlockSpec((block, LANE), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct(arrays[0].shape, arrays[0].dtype)
                 for _ in range(n_out)]
    return pl.pallas_call(
        functools.partial(kernel, **kw),
        grid=grid,
        in_specs=[spec] * len(arrays),
        out_specs=[spec] * n_out if n_out > 1 else spec,
        out_shape=out_shape if n_out > 1 else out_shape[0],
        interpret=interpret,
    )(*arrays)


def _axpy_block(shape, dtype):
    """The (rows, cols) block of a leaf's last two dims: whole where a
    dim fits, else cols 1024 and as many rows as fill AXPY_BLOCK_ELEMS
    of VMEM (lanes padded to 128) in whole sublane tiles (8 rows of fp32,
    16 of bf16).  Three operands, double-buffered, stay under 16 MiB."""
    rows, cols = shape[-2:]
    bc = min(cols, AXPY_BLOCK_COLS)
    sub = 32 // jnp.dtype(dtype).itemsize
    cap = max(sub, AXPY_BLOCK_ELEMS // (pl.cdiv(bc, LANE) * LANE) // sub * sub)
    return min(rows, cap), bc


def fused_axpy(x, y, a, interpret=False):
    """x + a·y for same-shape, same-dtype x and y, in their own layout.
    A 0-D or 1-D leaf is viewed as one row."""
    shape = x.shape
    view = (1,) * (2 - x.ndim) + shape
    x, y = x.reshape(view), y.reshape(view)
    *lead, rows, cols = view
    br, bc = _axpy_block(view, x.dtype)
    spec = pl.BlockSpec((*[pl.squeezed] * len(lead), br, bc), lambda *i: i)
    out = pl.pallas_call(
        functools.partial(_axpy_kernel, a=a),
        grid=(*lead, pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(view, x.dtype),
        interpret=interpret,
    )(x, y)
    return out.reshape(shape)


def local_update_2d(theta, g, m_bar, eta, interpret=False):
    return _tiled_call(_local_update_kernel, [theta, g, m_bar], 1,
                       interpret, eta=eta)


def server_update_2d(theta, m, delta_bar, gamma, alpha_eta, interpret=False):
    return _tiled_call(_server_update_kernel, [theta, m, delta_bar], 2,
                       interpret, gamma=gamma, alpha_eta=alpha_eta)
