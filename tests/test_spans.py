"""The program's spans and dispatch counter on the served chunk path.

* Every chunk of a :class:`ServeLoop` opens ``serve.put`` and
  ``serve.call`` (both with the chunk's ``chunk`` id), and inside the call
  ``runner.step`` with ``runner.ingest`` / ``runner.dispatch`` /
  ``runner.obs_accum`` / ``runner.commit``, then ``serve.block``.  Under a
  profiler session they land on the trace's host plane as annotations
  named by their paths.
* ``runner.step_seconds`` and ``serve.call_seconds`` observe exactly the
  ``runner.step`` / ``serve.call`` span durations.
* ``runner.dispatches`` counts the device programs a chunk launches: the
  fused step and the metrics accumulator on the sparse body, the step
  alone on the dense one.
* Under ``obs.disabled()`` or ``Metrics(enabled=False)`` a span records
  nothing: no aggregate, no annotation.
"""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import compile as qc
from repro.core.frontend import TStream
from repro.core.stream import SnapshotGrid
from repro.engine import ExecPolicy, Runner
from repro.obs import Metrics
from repro.serve import ServeLoop

SEG, SPC = 8, 2
SPAN = SEG * SPC
CHUNK_SPANS = ["serve.put", "serve.call", "serve.call/runner.step",
               "serve.call/runner.step/runner.ingest",
               "serve.call/runner.step/runner.dispatch",
               "serve.call/runner.step/runner.obs_accum",
               "serve.call/runner.step/runner.commit",
               "serve.call/serve.block"]


def _runner(body="sparse", metrics=None):
    s = TStream.source("in", prec=1)
    q = s.window(4).mean().join(s, lambda m, x: x - m)
    exe = qc.compile_query(q.node, out_len=SEG, pallas=False,
                           sparse=body == "sparse")
    return Runner(exe, ExecPolicy(body=body), segs_per_chunk=SPC,
                  metrics=metrics)


def _chunks(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"in": SnapshotGrid(
                value=rng.integers(0, 9, SPAN).astype(np.float32),
                valid=np.ones(SPAN, bool), t0=i * SPAN, prec=1)}
            for i in range(n)]


def _host_events(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("serve.", "runner.")):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    dict(e.stats).get("chunk")))
    return out


def test_spans_nest_and_reach_the_profiler_with_chunk_ids(tmp_path):
    loop = ServeLoop(_runner())
    chunks = _chunks(4)
    loop.step(chunks[0])                     # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs = list(loop.serve(iter(chunks[1:])))
    finally:
        jax.profiler.stop_trace()
    assert len(outs) == 3
    events = _host_events(str(tmp_path))
    names = {n for n, *_ in events}
    assert set(CHUNK_SPANS) <= names, names
    calls = sorted((s, e, c) for n, s, e, c in events if n == "serve.call")
    puts = sorted(c for n, s, e, c in events if n == "serve.put")
    assert [c for *_, c in calls] == [1, 2, 3]    # chunk 0 was put untraced
    assert puts == [1, 2, 3]
    # every runner span and every block nests inside exactly one call
    for n, s, e, _ in events:
        if n.startswith(("serve.call/", "runner.")):
            assert sum(cs <= s and e <= ce for cs, ce, _ in calls) == 1, n
    # the aggregates see the same paths, one per served chunk
    spans = loop.metrics.snapshot()["spans"]
    assert spans["serve.call/runner.step/runner.dispatch"]["count"] == 4
    assert spans["serve.put"]["count"] == 4


def test_step_and_call_seconds_come_from_their_spans():
    loop = ServeLoop(_runner())
    for _ in loop.serve(iter(_chunks(3))):
        pass
    snap = loop.metrics.snapshot()
    for hist, path in (("runner.step_seconds", "serve.call/runner.step"),
                       ("serve.call_seconds", "serve.call")):
        h, sp = snap["histograms"][hist], snap["spans"][path]
        assert h["count"] == sp["count"] == 3
        assert h["sum"] == sp["total_s"]


@pytest.mark.parametrize("body,per_chunk", [("sparse", 2), ("dense", 1)])
def test_dispatches_per_chunk(body, per_chunk):
    r = _runner(body)
    for c in _chunks(3):
        r.step(c)
    snap = r.metrics.snapshot()["counters"]
    assert snap["runner.chunks"]["value"] == 3
    assert snap["runner.dispatches"]["value"] == 3 * per_chunk


def test_revision_steps_count_as_dispatches():
    r = _runner()
    r.enable_revision(4)
    chunks = _chunks(2)
    for c in chunks:
        r.step(c)
    before = r.metrics.snapshot()["counters"]["runner.dispatches"]["value"]
    r.revise(0, chunks, [np.ones(SPC, bool)] * 2)
    after = r.metrics.snapshot()["counters"]["runner.dispatches"]["value"]
    assert after - before == 2


@pytest.mark.parametrize("how", ["obs.disabled", "Metrics(enabled=False)"])
def test_disabled_metrics_record_no_span(tmp_path, how):
    metrics = Metrics(enabled=False) if how != "obs.disabled" else None
    loop = ServeLoop(_runner(metrics=metrics))
    chunks = _chunks(3)
    loop.step(chunks[0])                     # compiles outside the trace
    off = (obs.disabled() if how == "obs.disabled"
           else contextlib.nullcontext())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with off:
            for _ in loop.serve(iter(chunks[1:])):
                pass
    finally:
        jax.profiler.stop_trace()
    assert _host_events(str(tmp_path)) == []
    spans = loop.metrics.tracer.span_report()
    want = 1 if how == "obs.disabled" else 0   # the untraced first chunk
    assert spans.get("serve.call", {"count": 0})["count"] == want

