"""Operations and bytes of one call of the causal flash-attention forward
kernel (``kernels/flash_attention.py``).

The call takes q (B·H, L, D) and k, v (B·Hk, L, D) and writes o (B·H, L, D).
Operations count the causal (query, key) pairs only, L·(L + 1)/2 for each
of the B·H heads, at 2·D for q·k and 2·D for p·v: the blocks the kernel
skips above the diagonal, and the masked half of the diagonal blocks it
does compute, are not work.  Bytes are the least the call must move: each
operand read once and the output written once.
"""
from __future__ import annotations


def flops(bh: int, seq_len: int, head_dim: int) -> float:
    pairs = seq_len * (seq_len + 1) / 2
    return 4.0 * bh * pairs * head_dim


def bytes_moved(bh: int, bkv: int, seq_len: int, head_dim: int,
                itemsize: int) -> float:
    return float(itemsize * seq_len * head_dim * (2 * bh + 2 * bkv))


def least_time_s(bh, bkv, seq_len, head_dim, itemsize, peak) -> float:
    """The larger of operations over peak bf16 FLOP/s and bytes over peak
    HBM bandwidth."""
    return max(flops(bh, seq_len, head_dim) / peak["bf16_flops_per_s"],
               bytes_moved(bh, bkv, seq_len, head_dim, itemsize)
               / peak["hbm_bytes_per_s"])
