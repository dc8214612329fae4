"""Phase tracing: wall-time span trees and a recompile detector.

Spans answer "where does the wall time go" at phase granularity —
serve / ingest / dispatch / commit, plan / compile / refit — without a
profiler run.  ``span()`` is a context manager; nesting builds
slash-separated paths (``serve.call/runner.step``), and each path
aggregates count / total / max seconds.  This is *host* wall time around
dispatch boundaries: spans never touch device values, so they are safe
anywhere, including around the transfer-guarded hot path.

Every span also opens a ``jax.profiler.TraceAnnotation`` named by its
path, with the span's keyword ids (``chunk=7``) as annotation metadata.
It records nothing unless a profiler session is active; under one, the
spans land on the host plane of the trace, on the profiler's clock, next
to the device's ops.  Under :func:`repro.obs.disabled` or
``Metrics(enabled=False)`` a span is a no-op: no clock read, no
annotation, no aggregate.

The recompile detector rides the engine's own staging discipline: every
jit-cache miss in ``Runner``'s ``step_cache`` (one entry per (policy,
geometry) point) calls :meth:`Tracer.record_compile` with the cache key.
A key compiled **more than once** means the cache was dropped and
rebuilt — an unexpected retrace; :meth:`Tracer.retraces` surfaces
exactly those.  The runner additionally cross-checks jax's own cache via
``jitted._cache_size()`` at snapshot time (``runner.jit_entries`` gauge),
which catches shape-driven retraces *inside* one staged step.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional

import jax

from .metrics import _on

__all__ = ["Tracer"]


class SpanTime:
    """What a span measured: its wall seconds, set when it closes (0.0
    for a span that did not record)."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


class Tracer:
    """Aggregating span recorder + per-key compile counter."""

    def __init__(self, on: Optional[Callable[[], bool]] = None):
        # whether spans record: the owning registry's switch, else the
        # module-wide one (obs.disabled())
        self._on = on if on is not None else _on
        self._stack: List[str] = []
        self._spans: Dict[str, Dict] = {}
        self._compiles: Dict[str, int] = {}
        self._aot: Dict[str, str] = {}

    @contextlib.contextmanager
    def span(self, name: str, **ids):
        """Time a phase.  Nested spans build ``outer/inner`` paths; the
        profiler annotation carries ``ids`` (e.g. ``chunk=7``).  Yields a
        :class:`SpanTime` whose ``seconds`` is set on exit."""
        took = SpanTime()
        if not self._on():
            yield took
            return
        path = "/".join(self._stack + [name])
        self._stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(path, **ids):
                yield took
        finally:
            took.seconds = dt = time.perf_counter() - t0
            self._stack.pop()
            s = self._spans.setdefault(
                path, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            s["count"] += 1
            s["total_s"] += dt
            s["max_s"] = max(s["max_s"], dt)

    def record_compile(self, key: str) -> None:
        """Note a jit-cache miss at a policy point (a staged compile)."""
        self._compiles[key] = self._compiles.get(key, 0) + 1

    def record_aot(self, key: str, how: str = "loaded") -> None:
        """Note an AOT executable installed under a staging key
        (``how``: ``"loaded"`` from a persisted cache or ``"compiled"``
        ahead of time).  The complement of :meth:`record_compile`: a warm
        serving start shows AOT loads here and *no* compile records — the
        tracer-verified zero-compile warm-start proof."""
        self._aot[key] = how

    def aot_installs(self) -> Dict[str, str]:
        return dict(self._aot)

    def compiles(self) -> Dict[str, int]:
        return dict(self._compiles)

    def retraces(self) -> Dict[str, int]:
        """Keys compiled more than once — unexpected retraces: the
        runner's step_cache holds exactly one step per key, so a second
        compile means the cache was dropped and the step re-staged."""
        return {k: n - 1 for k, n in self._compiles.items() if n > 1}

    def retrace_findings(self) -> List[Dict]:
        """The runtime retrace record in static-finding form: one entry
        per key compiled more than once, shaped like a
        ``repro.analysis`` finding payload (the recompile-hazard pass
        merges these with its static probe, so a runtime-observed retrace
        and a statically-proven under-keyed cache land in one report)."""
        return [{"severity": "error", "code": "runtime-retrace",
                 "message": (f"staging key {k!r} compiled {n + 1} times — "
                             "the step cache was dropped or under-keyed"),
                 "provenance": k}
                for k, n in sorted(self.retraces().items())]

    def span_report(self) -> Dict[str, Dict]:
        return {k: dict(v) for k, v in sorted(self._spans.items())}

    def compile_report(self) -> Dict:
        return {"counts": self.compiles(), "retraces": self.retraces(),
                "aot_installs": self.aot_installs()}

    def reset(self) -> None:
        self._spans.clear()
        self._compiles.clear()
        self._aot.clear()
        self._stack.clear()
