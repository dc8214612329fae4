"""The comparison that decides ``correct``: what the served path emitted
against the plain reference (``bench/refs/<app>.py``), over the sampled
keys and every served chunk.

Two numbers, each with the limit the configuration file states:

* ``value_gap``: the widest gap between an emitted value and the
  reference's, as a share of ``scale`` (the size of the terms whose
  difference the output is), over the ticks both call valid;
* ``flag_flips``: ticks whose validity differs from the reference's,
  leaving out only those where the reference's value lies within
  ``value_gap``'s limit of the filter's edge (a rounding there may flip
  the filter).  An exact count: its limit is 0.
"""
from __future__ import annotations

import numpy as np

__all__ = ["compare", "verdict"]


def compare(got_value, got_valid, ref: dict, gap_limit: float) -> dict:
    """Readings of one comparison (see the module docstring)."""
    gv = np.asarray(got_value, np.float64)
    gm = np.asarray(got_valid, bool)
    rv, rm = ref["value"], ref["valid"]
    scale = np.maximum(ref["scale"], np.finfo(np.float64).tiny)
    both = gm & rm
    gap = np.abs(gv - rv)[both] / scale[both]
    edge = ref["pre_valid"] & (np.abs(rv) <= gap_limit * scale)
    flips = (gm != rm) & ~edge
    return {"value_gap": float(gap.max()) if gap.size else 0.0,
            "flag_flips": int(flips.sum()),
            "compared": int(both.sum()), "flags": int(rm.sum())}


def verdict(readings: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})`` for every limited number."""
    shown = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
