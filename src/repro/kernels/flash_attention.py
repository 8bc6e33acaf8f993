"""Causal flash attention (GQA + optional sliding window) as a Pallas TPU
kernel.

TPU adaptation of the FlashAttention blocking: the grid is
(batch·kv_heads, q_blocks, k_blocks) with the K dimension innermost — on
TPU the grid is executed sequentially per core, so the online-softmax
running state (m, l, acc) lives in VMEM scratch carried across the k
iterations of one (kv head, q block) cell.  A cell holds all ``group`` q
heads of its KV head, stacked into one (group·block_q, D) operand, so each
K/V block is fetched once per group rather than once per q head.

The block sizes follow from the shapes (``_blocks``): as many q rows and
keys a step as a fixed VMEM budget allows, so a call takes few grid
steps.
Out-of-band K blocks (above the causal diagonal, or behind the sliding
window) are skipped with ``pl.when``, and their index maps are clamped to
the nearest visible block, so the pipeline sees an unchanged block index
and fetches nothing for them.  The sliding-window variant therefore does
O(L·W) work, which is what makes the long_500k dense variants
sub-quadratic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ROWS_TARGET = 1024             # q rows (group · block_q) a grid step
KEYS_TARGET = 1024             # keys (block_k) a grid step
ROW_BYTES = 5 << 20            # fp32 a step holds over its q rows: each
                               # row's scores, q and accumulator


def _band(qi, *, block_q, block_k, n_kb, causal, window):
    """The first and last k blocks q block `qi` sees."""
    q_start = qi * block_q
    lo = 0
    if window > 0:   # keys > q - window: the first row's oldest key
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
    hi = n_kb - 1
    if causal:       # keys <= q: the last row's newest key
        hi = jnp.minimum((q_start + block_q - 1) // block_k, hi)
    return lo, hi


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, n_kb, causal, window):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    group, _, D = q_ref.shape
    rows = group * block_q

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    lo, hi = _band(qi, block_q=block_q, block_k=block_k, n_kb=n_kb,
                   causal=causal, window=window)

    @pl.when(jnp.logical_and(ki >= lo, ki <= hi))
    def _compute():
        # row r holds q head r // block_q at position q_start + r % block_q
        q = q_ref[...].astype(jnp.float32).reshape(rows, D) * scale
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        s = q @ k.T                                       # (rows, bk)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_q, block_k), 1).reshape(s.shape)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        corr = jnp.exp(m_prev - m_cur)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + p @ v
        m_scr[...] = m_cur

    @pl.when(ki == n_kb - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).reshape(
            group, block_q, D).astype(o_ref.dtype)


def _fit(n, target, step):
    """The largest block of `n` that is at most `target`: `n` itself where
    it fits, else the largest multiple of `step` dividing `n`, else
    `step` (Pallas masks the ragged last block)."""
    if n <= target:
        return n
    for b in range(target - target % step, step - 1, -step):
        if n % b == 0:
            return b
    return step


def _blocks(L, D, group, window, itemsize):
    """(block_q, block_k) for a call: ROWS_TARGET q rows over the group and
    KEYS_TARGET keys a step, fewer where the rows' fp32 values would
    outgrow ROW_BYTES or the sliding window would leave most keys
    masked."""
    sub = 8 * 4 // itemsize            # sublane tile of the operand dtype
    keys = KEYS_TARGET
    if window > 0:
        keys = min(keys, max(128, pl.next_power_of_2(window)))
    block_k = _fit(L, keys, 128)
    rows = min(ROWS_TARGET, ROW_BYTES // (4 * (block_k + 2 * D)))
    per_head = max(rows // group, sub)
    if window > 0:
        per_head = min(per_head, max(128, pl.next_power_of_2(window)))
    block_q = _fit(L, per_head - per_head % sub, sub)
    return block_q, block_k


def flash_attention(q, k, v, *, causal=True, window=0, block_q=None,
                    block_k=None, interpret=False):
    """q (B,H,L,D); k/v (B,Hk,L,D) -> (B,H,L,D).  `block_q` (q rows per
    head) and `block_k` default to what ``_blocks`` picks from the
    shapes."""
    B, H, Lq, D = q.shape
    Hk = k.shape[1]
    group = H // Hk
    scale = D ** -0.5
    auto_q, auto_k = _blocks(Lq, D, group, window, q.dtype.itemsize)
    block_q = min(block_q or auto_q, Lq)
    block_k = min(block_k or auto_k, Lq)
    n_qb = pl.cdiv(Lq, block_q)
    n_kb = pl.cdiv(Lq, block_k)
    rows = group * block_q

    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * Hk, Lq, D)
    vr = v.reshape(B * Hk, Lq, D)

    band = functools.partial(_band, block_q=block_q, block_k=block_k,
                             n_kb=n_kb, causal=causal, window=window)

    def kv_index(b, qi, ki):
        # an out-of-band step keeps the last visible block: no new DMA
        return b, jnp.clip(ki, *band(qi)), 0

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kb=n_kb, causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hk, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((group, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((group, block_q, D),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Lq, D)
