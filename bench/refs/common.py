"""Arithmetic shared by the plain references: float64 window sums over a
keyed grid, and the rounding that stands for a lower storage precision."""
from __future__ import annotations

import ml_dtypes
import numpy as np

__all__ = ["window_sum", "rounder"]


def window_sum(x: np.ndarray, win: int) -> np.ndarray:
    """``out[:, i] = x[:, max(0, i - win + 1) : i + 1].sum(axis=1)``, by a
    float64 prefix sum (ticks before the stream are empty)."""
    p = np.zeros((x.shape[0], x.shape[1] + 1), np.float64)
    np.cumsum(x, axis=1, dtype=np.float64, out=p[:, 1:])
    lo = np.maximum(np.arange(1, x.shape[1] + 1) - win, 0)
    return p[:, 1:] - p[:, lo]


def rounder(precision: str):
    """Identity for ``float64``; otherwise round every stream to that
    type and back, as a pipeline that stores each operator's output at
    that precision would (sums are still accumulated in float64)."""
    if precision == "float64":
        return lambda a: a
    dt = {"bfloat16": ml_dtypes.bfloat16}[precision]
    return lambda a: np.asarray(a).astype(dt).astype(np.float64)
