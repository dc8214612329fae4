"""The one traffic generator: a keyed event pool and its arrival schedule,
both read from a traffic mix's data file (``bench/traffic/<mix>.json``).

A mix names three parts, each ``{"kind": <kind>, ...parameters}``, and
each kind is a file of its own, found by name:

* ``values``: how an event's value is drawn
  (``bench/kinds/values/<kind>.py``, ``draw(rng, spec, shape)``);
* ``activity``: which (key, tick) cells carry an event
  (``bench/kinds/activity/<kind>.py``, ``draw(rng, spec, shape)``).
  Idle ticks are the null value: ``valid`` false and value 0;
* ``pacing``: when each chunk is due (``bench/kinds/pacing/<kind>.py``,
  ``schedule(spec, span, seconds)``, ``None`` when the mix is
  backlogged);

and ``pool_chunks``: how many distinct chunks are generated; the served
stream cycles through them while its clock advances.  A new mix with
known kinds is a data file alone; a new kind is one new file.

Everything is vectorised numpy on the host, where stream input lives, and
follows from the seed alone.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import plugins

__all__ = ["Pool", "make_pool", "stream_rows", "schedule"]


@dataclasses.dataclass(frozen=True)
class Pool:
    value: np.ndarray    # float32 (P, K, span)
    valid: np.ndarray    # bool (P, K, span)

    @property
    def chunks(self) -> int:
        return self.value.shape[0]

    @property
    def span(self) -> int:
        return self.value.shape[2]

    def events(self, c: int) -> int:
        """Valid events in stream chunk ``c``."""
        return int(self._counts[c % self.chunks])

    def __post_init__(self):
        object.__setattr__(self, "_counts",
                           self.valid.sum(axis=(1, 2), dtype=np.int64))


def kind(part: str, spec: dict):
    """The module of ``spec``'s kind of ``part`` (values, activity,
    pacing)."""
    try:
        return plugins.load("kinds", part, spec["kind"])
    except FileNotFoundError:
        raise ValueError(f"unknown {part} kind {spec['kind']!r}: no "
                         f"bench/kinds/{part}/{spec['kind']}.py") from None


def make_pool(traffic: dict, keys: int, span: int, seed: int) -> Pool:
    """The seed's event pool: ``pool_chunks`` chunks of ``keys x span``."""
    rng = np.random.default_rng(seed)
    shape = (int(traffic["pool_chunks"]), keys, span)
    value = kind("values", traffic["values"]).draw(
        rng, traffic["values"], shape)
    valid = kind("activity", traffic["activity"]).draw(
        rng, traffic["activity"], shape)
    value[~valid] = 0.0
    return Pool(value=value, valid=valid)


def stream_rows(pool: Pool, rows, n_chunks: int):
    """The served stream of keys ``rows`` over chunks ``0 .. n_chunks-1``
    as ``(value (k, n_chunks*span), valid)`` — what the reference reads."""
    idx = np.arange(n_chunks) % pool.chunks
    v = pool.value[:, rows][idx]                  # (n, k, span)
    m = pool.valid[:, rows][idx]
    k = len(rows)
    return (v.transpose(1, 0, 2).reshape(k, -1),
            m.transpose(1, 0, 2).reshape(k, -1))


def schedule(pacing: dict, span: int, seconds: float):
    """Open-loop schedule of a window of ``seconds``, in seconds after it
    opens: ``(chunk due (n,), tick due (n, span))``; ``None`` for a
    backlogged mix."""
    return kind("pacing", pacing).schedule(pacing, span, seconds)
