"""Reduce one profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, device time per operation, and the idle
gaps named by what the host was doing.

* The window is the host span ``bench.window`` the harness opens around
  the measured loop; everything is clipped to it.
* A device's busy time is the union of the intervals of its ``XLA Ops``
  events (the operations that ran on it) inside the window.
* An operation's device time is its self time: a conditional or loop
  event encloses the events of the operations in its body, which are
  subtracted.  Operations are named by their HLO instruction name (the
  event's text up to `` = ``); the full text, with the shapes, is kept
  for the readers that compute bytes from shapes.
* An idle gap is a stretch of the window with no operation on the device.
  It is named by the innermost ``bench.*`` host span that covers its
  middle (``bench.window`` itself when no other does).

Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import collections
import glob
import os
import re

__all__ = ["find_xplane", "load", "summarize", "reduce_planes"]

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> dict:
    """The parts of the trace the reduction needs, as plain data:
    ``{"host": [(name, start_ns, end_ns)], "devices": {id: [(name,
    start_ns, end_ns)]}}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    return {"host": host, "devices": devices}


def short_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return text.split(" = ", 1)[0]


def _self_times(ops, lo, hi):
    """``{text: self seconds}`` of nested events clipped to the window:
    each event's time less the time of the events nested in it."""
    own = collections.Counter()
    stack = []                                    # (end, text)
    for text, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        dur = max(0.0, min(e, hi) - max(s, lo))
        own[text] += dur
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((e, text))
    return {t: v * 1e-9 for t, v in own.items()}


def _union(intervals, lo, hi):
    """Merged intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(busy, lo, hi):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _namer(host, lo, hi):
    """Name of the innermost harness span covering a time."""
    spans = sorted((s, e, n) for n, s, e in host
                   if n != WINDOW and e > lo and s < hi)

    def name(t):
        best = None
        for s, e, n in spans:
            if s > t:
                break
            if e >= t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else WINDOW
    return name


def reduce_planes(data: dict, device_ids) -> dict:
    """Per-device busy and idle figures over the window, device time per
    operation name (mean over the devices), and the idle gaps by host
    span (mean over the devices).  Times in seconds."""
    wins = [(s, e) for n, s, e in data["host"] if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = wins[0]
    window_s = (hi - lo) * 1e-9
    name = _namer(data["host"], lo, hi)
    per_dev, op_time, gap_time = {}, collections.Counter(), \
        collections.Counter()
    calls, text = collections.Counter(), {}
    n = len(device_ids)
    for d in device_ids:
        ops = data["devices"].get(d, [])
        busy = _union(((s, e) for _, s, e in ops), lo, hi)
        busy_ns = sum(e - s for s, e in busy)
        for t, sec in _self_times(ops, lo, hi).items():
            op_time[short_name(t)] += sec / n
            text.setdefault(short_name(t), t)
        for t, s, e in ops:
            if lo <= s < hi:
                calls[short_name(t)] += 1 / n
        for s, e in _gaps(busy, lo, hi):
            gap_time[name((s + e) / 2)] += (e - s) * 1e-9 / n
        per_dev[d] = {"busy_s": busy_ns * 1e-9,
                      "idle_share": 1.0 - busy_ns * 1e-9 / window_s,
                      "ops": len(ops)}
    return {"window_s": window_s,
            "busy_s": sum(v["busy_s"] for v in per_dev.values()) / n,
            "devices": per_dev, "op_s": dict(op_time), "op_text": text,
            "op_calls": dict(calls),
            "gap_s": dict(gap_time),
            "breakdown": {
                "device_ops": [[k, v] for k, v in op_time.most_common(10)],
                "idle_gaps": [[k, v] for k, v in gap_time.most_common(10)]}}


def summarize(path: str, device_ids) -> dict:
    return reduce_planes(load(path), device_ids)
