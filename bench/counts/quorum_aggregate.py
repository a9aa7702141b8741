"""Operations and bytes of one quorum merge, from its operand shapes.

``out (B, C) = bias + sum over arrived slots k of portion_k (B, Dk) @ W_k``:
2 operations per multiply-add of the arrived slots; the least bytes are
the arrived slots' portions and FC slices, the bias and the output, all
float32.
"""
from __future__ import annotations


def flops(arrived: int, rows: int, dk: int, classes: int) -> float:
    return float(2 * arrived * rows * dk * classes)


def bytes_moved(arrived: int, rows: int, dk: int, classes: int) -> float:
    return float(4 * (arrived * (rows * dk + dk * classes)
                      + classes + rows * classes))
