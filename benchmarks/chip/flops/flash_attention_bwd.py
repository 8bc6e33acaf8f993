"""Operations and bytes of one backward of the causal flash attention: a
``_flash_dkv_kernel`` call and its ``_flash_dq_kernel`` call
(``kernels/flash_attention.py``).

The pair takes q and dO (B·H, L, D) and k, v (B·Hk, L, D), and writes dq
(B·H, L, D) and dk, dv (B·Hk, L, D).  Operations count the causal
(query, key) pairs only, L·(L + 1)/2 for each of the B·H heads, at 2·D for
each of the backward's four products (dO·vᵀ, pᵀ·dO, ds·k and dsᵀ·q): the
recomputed q·kᵀ, the blocks the kernels skip and the masked half of the
diagonal blocks are not work.  Bytes are the least the pair must move:
q, dO and dq, and k, v, dk and dv, each once.
"""
from __future__ import annotations


def flops(bh: int, seq_len: int, head_dim: int) -> float:
    pairs = seq_len * (seq_len + 1) / 2
    return 8.0 * bh * pairs * head_dim


def bytes_moved(bh: int, bkv: int, seq_len: int, head_dim: int,
                itemsize: int) -> float:
    return float(itemsize * seq_len * head_dim * (3 * bh + 4 * bkv))


def least_time_s(bh, bkv, seq_len, head_dim, itemsize, peak) -> float:
    """The larger of operations over peak bf16 FLOP/s and bytes over peak
    HBM bandwidth."""
    return max(flops(bh, seq_len, head_dim) / peak["bf16_flops_per_s"],
               bytes_moved(bh, bkv, seq_len, head_dim, itemsize)
               / peak["hbm_bytes_per_s"])
