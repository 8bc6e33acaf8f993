"""Operations and bytes of one call of the fused x + a·y kernel
(``kernels/fedadc_update.py`` ``fused_axpy_2d``): it reads x and y and
writes the result, n elements each, with 2 operations an element.  It is
bound by memory."""
from __future__ import annotations


def flops(n: int) -> float:
    return 2.0 * n


def bytes_moved(n: int, itemsize: int) -> float:
    return 3.0 * n * itemsize


def least_time_s(n, itemsize, peak) -> float:
    return max(flops(n) / peak["bf16_flops_per_s"],
               bytes_moved(n, itemsize) / peak["hbm_bytes_per_s"])
