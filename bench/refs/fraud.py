"""Plain reference of the fraud-detection query (TiLT paper, App. A):
flag a transaction above mean + 3 standard deviations of the card's
trailing ``win`` ticks, shifted one tick so a transaction does not mask
itself.  The semantics are those of the event-centric pipeline the app
carries: window aggregates over the valid events of the last ``win``
ticks (empty windows are null), population standard deviation, a join
valid where both sides are, and a filter that keeps the excess."""
from __future__ import annotations

import numpy as np

from refs.common import rounder, window_sum


def reference(value, valid, *, win: int, precision: str = "float64"):
    """``value``, ``valid``: ``(k, T)``.  Returns the output stream as
    ``value`` (the excess ``x - thr`` at every tick), ``valid`` (the
    flag), ``pre_valid`` (where the excess is defined, before the filter)
    and ``scale`` (``|x| + |thr|``, the size of the terms whose difference
    is the excess)."""
    r = rounder(precision)
    m = np.asarray(valid, bool)
    x = r(np.where(m, np.asarray(value, np.float64), 0.0))
    cnt = window_sum(m.astype(np.float64), win)
    s1 = window_sum(x, win)
    s2 = window_sum(x * x, win)
    ok = cnt > 0
    c = np.maximum(cnt, 1.0)
    mu_raw = s1 / c
    mu = r(mu_raw)
    sd = r(np.sqrt(np.maximum(s2 / c - mu_raw * mu_raw, 0.0)))
    # shift one tick later: the threshold at tick i comes from tick i-1
    thr = np.zeros_like(mu)
    thr[:, 1:] = r(mu[:, :-1] + 3.0 * sd[:, :-1])
    thr_ok = np.zeros_like(ok)
    thr_ok[:, 1:] = ok[:, :-1]
    ex = r(x - thr)
    pre = m & thr_ok
    return {"value": ex, "valid": pre & (ex > 0), "pre_valid": pre,
            "scale": np.abs(x) + np.abs(thr)}
