"""Plain reference of the trend-based trading query (TiLT paper, App. A):
the difference of a symbol's mean price over the last ``short`` and the
last ``long`` ticks, kept where it is positive (an uptrend).  Window means
are over the valid quotes in the window (empty windows are null); the
join is valid where both means are."""
from __future__ import annotations

import numpy as np

from refs.common import rounder, window_sum


def reference(value, valid, *, short: int, long: int,
              precision: str = "float64"):
    """``value``, ``valid``: ``(k, T)``.  Returns ``value`` (the
    difference at every tick), ``valid`` (uptrend), ``pre_valid`` (where
    the difference is defined) and ``scale`` (``|mean_short| +
    |mean_long|``)."""
    r = rounder(precision)
    m = np.asarray(valid, bool)
    x = r(np.where(m, np.asarray(value, np.float64), 0.0))
    mf = m.astype(np.float64)

    def mean(w):
        cnt = window_sum(mf, w)
        return r(window_sum(x, w) / np.maximum(cnt, 1.0)), cnt > 0

    a_s, ok_s = mean(short)
    a_l, ok_l = mean(long)
    d = r(a_s - a_l)
    pre = ok_s & ok_l
    return {"value": d, "valid": pre & (d > 0), "pre_valid": pre,
            "scale": np.abs(a_s) + np.abs(a_l)}
