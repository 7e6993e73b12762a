"""Bytes a kernel must move, and shares of the chip's roofline.

The fold kernels (``block_sketch``, ``plan``) must read each block once:
``rows x columns x itemsize`` bytes; their outputs (a few moments and a
histogram per column) are negligible beside it.  The shuffle
(``rsp_shuffle``) must read each original block once and write it once; the
one-hot matmul it runs the intra-tile permutation on is an artifact of the
implementation and is not counted.  All three are bound by memory
bandwidth: their necessary arithmetic is a few operations per byte.
"""

from __future__ import annotations


def fold_bytes(rows: int, columns: int, itemsize: int = 4) -> int:
    return rows * columns * itemsize


def shuffle_bytes(rows: int, columns: int, itemsize: int = 4) -> int:
    return 2 * rows * columns * itemsize


def least_seconds(nbytes: float, bytes_per_s: float) -> float:
    """The least time the chip could take to move ``nbytes``."""
    return nbytes / bytes_per_s


def share_pct(least_s: float, seconds: float) -> float | None:
    """``least_s`` as a share of the time taken, in percent (None when
    nothing was timed)."""
    if seconds <= 0:
        return None
    return 100.0 * least_s / seconds
