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

The backward runs as two kernels on the same blocking, recomputing the
probabilities from the forward's per-row log-sum-exp, which the forward
writes lane-major as (B·Hk, group, L) when asked for it:
``_flash_dkv_kernel`` (grid (B·Hk, k_blocks, q_blocks), q innermost)
accumulates dk and dv of one K/V block over the group's q heads and the q
blocks that see it; ``_flash_dq_kernel`` (the forward's grid) accumulates
dq.  Neither holds more than one (block_k, block_q) tile of scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # dot_general: a @ b.T
ROWS_TARGET = 1024             # q rows (group · block_q) a grid step
KEYS_TARGET = 1024             # keys (block_k) a grid step
ROW_BYTES = 5 << 20            # fp32 a step holds over its q rows: each
                               # row's scores, q and accumulator
BWD_ROWS_TARGET = 256          # q rows of each head a backward step
BWD_KEYS_TARGET = 256          # keys a backward step


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


def _q_band(ki, *, block_q, block_k, n_qb, causal, window):
    """The first and last q blocks that see k block `ki`."""
    k_start = ki * block_k
    lo = 0
    if causal:       # queries >= k: the first key's own row
        lo = k_start // block_q
    hi = n_qb - 1
    if window > 0:   # queries < k + window: the last key's newest reader
        hi = jnp.minimum((k_start + block_k + window - 2) // block_q, hi)
    return lo, hi


def _mask(qpos, kpos, *, causal, window, seq_len=None):
    """The (query, key) pairs a row sees; with `seq_len`, only pairs inside
    the sequence (a ragged last block reads past its end)."""
    mask = jnp.ones(qpos.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    if seq_len is not None:
        mask &= jnp.maximum(qpos, kpos) < seq_len
    return mask


def _lanes(col):
    """(n, 1) -> (1, n): a column of row statistics, laid along lanes."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _column(row):
    """(1, n) -> (n, 1)."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, scale, block_q,
                  block_k, n_kb, causal, window):
    # the VJP's forward also writes each row's log-sum-exp to lse_ref
    lse_ref = refs[0] if len(refs) == 4 else None
    m_scr, l_scr, acc_scr = refs[-3:]
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
        if lse_ref is not None:
            lse = _lanes(m_scr[...] + jnp.log(denom))      # (1, rows)
            for h in range(group):
                lse_ref[0, h:h + 1, :] = lse[:, h * block_q:(h + 1) * block_q]


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                      dv_ref, dk_scr, dv_scr, *, scale, block_q, block_k,
                      n_qb, seq_len, causal, window):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    group = q_ref.shape[0]
    ragged = seq_len % block_q != 0

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    lo, hi = _q_band(ki, block_q=block_q, block_k=block_k, n_qb=n_qb,
                     causal=causal, window=window)

    @pl.when(jnp.logical_and(qi >= lo, qi <= hi))
    def _compute():
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        shape = (block_k, block_q)                        # scores, transposed
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = _mask(qpos, kpos, causal=causal, window=window,
                     seq_len=seq_len if ragged else None)
        # the group's q heads in turn, each summed into its K/V head's dk, dv
        for h in range(group):
            q = q_ref[h].astype(jnp.float32) * scale      # (bq, D)
            do = do_ref[h].astype(jnp.float32)
            if ragged:   # rows past the end: no products with what is there
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, 1), 0) < seq_len
                q, do = jnp.where(row, q, 0.0), jnp.where(row, do, 0.0)
            s = jax.lax.dot_general(k, q, _NT)            # (bk, bq)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0, h:h + 1, :]), 0.0)
            dp = jax.lax.dot_general(v, do, _NT)
            ds = jnp.where(mask, p * (dp - di_ref[0, h:h + 1, :]), 0.0)
            dv_scr[...] += p @ do
            dk_scr[...] += ds @ q

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                     dq_scr, lse_scr, di_scr, *, scale, block_q, block_k,
                     n_kb, seq_len, causal, window):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    group = q_ref.shape[0]
    ragged = seq_len % block_k != 0
    lo, hi = _band(qi, block_q=block_q, block_k=block_k, n_kb=n_kb,
                   causal=causal, window=window)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki == lo)
    def _columns():      # the q block's statistics, one row a sublane
        for h in range(group):
            lse_scr[h] = _column(lse_ref[0, h:h + 1, :])
            di_scr[h] = _column(di_ref[0, h:h + 1, :])

    @pl.when(jnp.logical_and(ki >= lo, ki <= hi))
    def _compute():
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        shape = (block_q, block_k)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = _mask(qpos, kpos, causal=causal, window=window,
                     seq_len=seq_len if ragged else None)
        if ragged:       # keys past the end: no products with what is there
            key = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) < seq_len
            k, v = jnp.where(key, k, 0.0), jnp.where(key, v, 0.0)
        for h in range(group):
            q = q_ref[h].astype(jnp.float32) * scale      # (bq, D)
            do = do_ref[h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, _NT)            # (bq, bk)
            p = jnp.where(mask, jnp.exp(s - lse_scr[h]), 0.0)
            dp = jax.lax.dot_general(do, v, _NT)
            ds = jnp.where(mask, p * (dp - di_scr[h]), 0.0)
            dq_scr[h] += ds @ k

    @pl.when(ki == n_kb - 1)
    def _finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


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


def _bwd_blocks(L, group, window):
    """(block_q, block_k) for the backward kernels: BWD_ROWS_TARGET q rows
    of each head and BWD_KEYS_TARGET keys a step, fewer where the sliding
    window would leave most keys masked.  Both are lane multiples (or L):
    the statistics' blocks lay q positions along lanes."""
    rows, keys = BWD_ROWS_TARGET, BWD_KEYS_TARGET
    if window > 0:
        cap = max(128, pl.next_power_of_2(window))
        rows, keys = min(rows, cap), min(keys, cap)
    return _fit(L, rows, 128), _fit(L, keys, 128)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=None,
                    block_k=None, interpret=False, residuals=False):
    """q (B,H,L,D); k/v (B,Hk,L,D) -> (B,H,L,D).  `block_q` (q rows per
    head) and `block_k` default to what ``_blocks`` picks from the
    shapes.  With `residuals`, also each row's log-sum-exp, (B, Hk,
    group, L) float32, for ``flash_attention_bwd``."""
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
    out_specs = pl.BlockSpec((group, block_q, D),
                             lambda b, qi, ki: (b, qi, 0))
    out_shape = jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype)
    if residuals:
        out_specs = [out_specs, pl.BlockSpec(
            (1, group, block_q), lambda b, qi, ki: (b, 0, qi))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((B * Hk, group, Lq),
                                                     jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(B * Hk, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((group, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    if residuals:
        out, lse = out
        return out.reshape(B, H, Lq, D), lse.reshape(B, Hk, group, Lq)
    return out.reshape(B, H, Lq, D)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        block_q=None, block_k=None, interpret=False):
    """The gradients (dq, dk, dv) of ``flash_attention`` at q (B,H,L,D),
    k/v (B,Hk,L,D), given its output o, its `lse` residual and the
    output's cotangent `do` (B,H,L,D).  `block_q` and `block_k` default
    to what ``_bwd_blocks`` picks from the shapes."""
    B, H, L, D = q.shape
    Hk = k.shape[1]
    group = H // Hk
    scale = D ** -0.5
    auto_q, auto_k = _bwd_blocks(L, group, window)
    block_q = min(block_q or auto_q, L)
    block_k = min(block_k or auto_k, L)
    n_qb = pl.cdiv(L, block_q)
    n_kb = pl.cdiv(L, block_k)

    qr = q.reshape(B * H, L, D)
    kr = k.reshape(B * Hk, L, D)
    vr = v.reshape(B * Hk, L, D)
    dor = do.reshape(B * H, L, D)
    lse = lse.reshape(B * Hk, group, L)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1).reshape(B * Hk, group, L)
    blocks = dict(scale=scale, block_q=block_q, block_k=block_k,
                  seq_len=L, causal=causal, window=window)

    # dk, dv: a K/V block a cell, its q blocks innermost
    q_band = functools.partial(_q_band, block_q=block_q, block_k=block_k,
                               n_qb=n_qb, causal=causal, window=window)

    def q_index(b, ki, qi):
        return b, jnp.clip(qi, *q_band(ki)), 0

    def stat_index(b, ki, qi):
        return b, 0, jnp.clip(qi, *q_band(ki))

    def kv_index(b, ki, qi):
        return b, ki, 0

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, n_qb=n_qb, **blocks),
        grid=(B * Hk, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((group, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((group, block_q, D), q_index),
            pl.BlockSpec((1, group, block_q), stat_index),
            pl.BlockSpec((1, group, block_q), stat_index),
        ],
        out_specs=[pl.BlockSpec((1, block_k, D), kv_index)] * 2,
        out_shape=[jax.ShapeDtypeStruct((B * Hk, L, D), k.dtype),
                   jax.ShapeDtypeStruct((B * Hk, L, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)] * 2,
        interpret=interpret,
    )(qr, kr, vr, dor, lse, di)

    # dq: the forward's grid, k blocks innermost
    band = functools.partial(_band, block_q=block_q, block_k=block_k,
                             n_kb=n_kb, causal=causal, window=window)

    def k_index(b, qi, ki):
        return b, jnp.clip(ki, *band(qi)), 0

    def row_index(b, qi, ki):
        return b, qi, 0

    def row_stat_index(b, qi, ki):
        return b, 0, qi

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, n_kb=n_kb, **blocks),
        grid=(B * Hk, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((group, block_q, D), row_index),
            pl.BlockSpec((1, block_k, D), k_index),
            pl.BlockSpec((1, block_k, D), k_index),
            pl.BlockSpec((group, block_q, D), row_index),
            pl.BlockSpec((1, group, block_q), row_stat_index),
            pl.BlockSpec((1, group, block_q), row_stat_index),
        ],
        out_specs=pl.BlockSpec((group, block_q, D), row_index),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((group, block_q, D), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lse, di)
    return (dq.reshape(B, H, L, D), dk.reshape(B, Hk, L, D),
            dv.reshape(B, Hk, L, D))
