"""Window and rate arithmetic, and the two window loops, on a fake clock."""
import types

import numpy as np
import pytest

import generate
import run
import window


def test_events_per_s_counts_whole_chunks():
    done = [1.5, 3.0, 4.5, 6.0]
    rate, n, span = window.events_per_s(0.0, done, [10, 10, 10, 10], 4.0)
    assert (n, span) == (3, 4.5) and rate == pytest.approx(30 / 4.5)
    rate, n, _ = window.events_per_s(0.0, done, [1, 2, 3, 4], 4.5)
    assert n == 3 and rate == pytest.approx(6 / 4.5)
    with pytest.raises(ValueError):
        window.events_per_s(0.0, done, [1] * 4, 10.0)


def test_weighted_percentile_is_the_expanded_sample_percentile():
    rng = np.random.default_rng(0)
    v = rng.random(50)
    w = rng.integers(0, 5, 50)
    expanded = np.repeat(v, w)
    for q in (50, 95, 99):
        assert window.weighted_percentile(v, w, q) == pytest.approx(
            np.percentile(expanded, q, method="inverted_cdf"))


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeService:
    """Serves each chunk in ``step_s`` of fake time."""

    def __init__(self, clock, step_s, keys=2, span=4):
        self.clock, self.step_s = clock, step_s
        self.keys, self.span = keys, span
        self.runner = types.SimpleNamespace(
            spec=types.SimpleNamespace(input_specs={"in": None}))

    def _out(self):
        self.clock.t += self.step_s
        return types.SimpleNamespace(value=np.zeros((self.keys, self.span)),
                                     valid=np.zeros((self.keys, self.span),
                                                    bool))

    def step(self, chunk):
        return self._out()

    def serve(self, source):
        for _ in source:
            yield self._out()


def _stream(svc, keys=2, span=4, chunks=3):
    pool = generate.Pool(value=np.zeros((chunks, keys, span), np.float32),
                         valid=np.ones((chunks, keys, span), bool))
    return run.Stream(svc, pool, np.arange(keys), run._no_spans)


def test_backlogged_window_on_a_fake_clock():
    clock = FakeClock()
    svc = FakeService(clock, 1.5)
    stream = _stream(svc)
    t_open, recs = run.run_backlogged(svc, stream, 4.0, run._no_spans,
                                      clock=clock)
    assert len(recs) == 3 and all(r["events"] == 8 for r in recs)
    rate, n, span = window.events_per_s(
        t_open, [r["emit"] for r in recs], [r["events"] for r in recs], 4.0)
    assert (n, span) == (3, 4.5) and rate == pytest.approx(24 / 4.5)
    assert len(stream.kept_value) == 3


def test_paced_window_on_a_fake_clock():
    """4 ticks per chunk at 4 ticks/s: a chunk is due every second.  A
    0.5 s step never queues; a 1.5 s step queues 0.5 s more per chunk."""
    for step_s, waits in ((0.5, [0, 0, 0]), (1.5, [0, 0.5, 1.0])):
        clock = FakeClock()
        svc = FakeService(clock, step_s)
        stream = _stream(svc)
        schedule = generate.schedule(
            {"kind": "open_loop",
             "phases": [{"seconds": 1.0, "ticks_per_s": 4}]}, 4, 3.0)
        t_open, recs = run.run_paced(svc, stream, schedule, run._no_spans,
                                     clock=clock, sleep=clock.sleep)
        tick_due = schedule[1]
        assert len(recs) == 3
        np.testing.assert_allclose([r["call"] - r["due"] for r in recs],
                                   waits)
        emitted = np.asarray([r["emit"] - t_open for r in recs])
        lat = window.event_latencies(tick_due, emitted)
        # the last tick of the first chunk waits only for its step
        assert lat[0, -1] == pytest.approx(step_s)
        assert lat[0, 0] == pytest.approx(step_s + 0.75)
