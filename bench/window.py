"""Window and rate arithmetic, kept apart from any clock so that tests can
drive it with a fake one.

* Backlogged cells: the window opens when the first chunk of the window
  is requested and closes at the completion of the first chunk that
  finishes at or after ``seconds``; ``events_per_s`` is the valid events
  of every chunk completed by then over that time, so the window is a
  whole number of chunks.
* Paced cells: every event is timed from its due time on the open-loop
  schedule to the emission of its chunk's result; percentiles are taken
  over all events of the window (each tick weighted by its valid events).
"""
from __future__ import annotations

import numpy as np

__all__ = ["events_per_s", "weighted_percentile", "event_latencies"]


def events_per_s(t_open: float, done: list, events: list,
                 seconds: float) -> tuple:
    """``done[i]``: completion time of window chunk ``i`` (one clock),
    ``events[i]`` its valid events.  Returns ``(rate, chunks counted,
    window length)``: chunks up to and including the first one done at or
    after ``t_open + seconds``."""
    n = next((i + 1 for i, t in enumerate(done) if t - t_open >= seconds),
             None)
    if n is None:
        raise ValueError("the window never reached its length")
    span = done[n - 1] - t_open
    return sum(events[:n]) / span, n, span


def weighted_percentile(values, weights, q: float) -> float:
    """The smallest value whose cumulative weight reaches ``q`` (0-100)
    of the total: the nearest-rank percentile of the expanded sample."""
    v = np.asarray(values, np.float64).ravel()
    w = np.asarray(weights, np.float64).ravel()
    keep = w > 0
    v, w = v[keep], w[keep]
    if not v.size:
        raise ValueError("no weighted values")
    order = np.argsort(v, kind="stable")
    cum = np.cumsum(w[order])
    i = int(np.searchsorted(cum, q / 100.0 * cum[-1], side="left"))
    return float(v[order][min(i, v.size - 1)])


def event_latencies(tick_due, emitted) -> np.ndarray:
    """Per tick ``(n, span)``: emission time of its chunk minus its due
    time (both in seconds after the window opened)."""
    return np.asarray(emitted, np.float64)[:, None] - np.asarray(tick_due)
