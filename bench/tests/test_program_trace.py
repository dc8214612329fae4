"""The reduction of the program's own names in a trace: the wire-format
read of the device planes, phases from ``tf_op``, per-chunk spans, and
the readers' ``None`` on a trace without the names."""
import os
import re
import shutil
import types

import pytest

import program_trace
import run
import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TREND = os.path.join(DATA, "trend_paced_small.xplane.pb")
NEW_READERS = ["gather_ms", "change_detect_ms", "compact_ms", "hold_ms",
               "completion_lag_ms", "host_call_ms"]


def test_wire_read_matches_profile_data():
    """The device ops read from the wire format are the ops
    ``ProfileData`` reads, to the nanosecond."""
    ours = program_trace.load(TREND)["devices"][0]["ops"]
    theirs = trace.load(TREND)["devices"][0]
    assert sorted((n, s, e) for n, s, e, _, _ in ours) == sorted(theirs)


def test_tf_op_of_the_recorded_trace():
    """PR 12's recorded trend.paced trace (a program with no scopes): the
    sliding-sum kernels' scope path is recovered, no op has a phase, and
    every op belongs to the one ``jit_step`` program."""
    data = program_trace.load(TREND)
    dev = data["devices"][0]
    kernels = {tf for n, _, _, tf, _ in dev["ops"]
               if trace.short_name(n).startswith("%vmap_jit_sliding_sum")}
    assert kernels == {"jit(step)/vmap(jit(sliding_sum))/pallas_call:"}
    assert all(program_trace.phase(tf) is None for *_, tf, _ in dev["ops"])
    assert {n.partition("(")[0] for n, _, _ in dev["modules"]} == {
        "jit_step"}
    assert program_trace.phase_s(data) is None
    assert program_trace.completion_lags(data) == []
    assert program_trace.host_call_s(data) == []


@pytest.mark.parametrize("tf_op,want", [
    ("jit(tilt_sparse_steady)/cond/branch_6_fun/tilt.gather/concatenate",
     "tilt.gather"),
    ("jit(tilt_sparse_steady)/tilt.change_detect/vmap(seg_dirty)/pallas_call",
     "tilt.change_detect"),
    ("jit(tilt_dense_step)/tilt.compute/vmap(jit(sliding_sum))/pallas_call",
     "tilt.compute"),
    ("jit(a)/tilt.hold/vmap(tilt.scatter)/gather", "tilt.scatter"),
    ("jit(step)/vmap(jit(sliding_sum))/pallas_call:", None),
    (None, None)])
def test_phase_is_the_innermost_scope(tf_op, want):
    assert program_trace.phase(tf_op) == want


def _ctx(cell, n_chunks=3):
    return types.SimpleNamespace(cell={"name": cell},
                                 chunks=[{}] * n_chunks, counters={})


def test_readers_read_none_without_the_names(monkeypatch, tmp_path):
    """The parent's trace (no spans, no ``jit_tilt_*`` programs, no
    scopes): every reader of the program's names reads ``None``."""
    cell = tmp_path / "trace" / "trend.paced"
    cell.mkdir(parents=True)
    shutil.copy(TREND, cell / "t.xplane.pb")
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    for name in NEW_READERS:
        assert run.reader(name).read(_ctx("trend.paced")) is None, name
    assert run.reader("gather_ms").read(_ctx("no.trace")) is None


def test_dispatches_per_chunk_reads_the_counters():
    read = run.reader("dispatches_per_chunk").read
    ctx = _ctx("fraud.quiet")
    assert read(ctx) is None
    ctx.counters = {"runner.chunks": 10, "runner.dispatches": 20}
    assert read(ctx) == 2.0


def test_chunk_spans_by_hand():
    """Per chunk: host time in put + call outside block, and the lag from
    the end of the chunk's step module to the end of its block (the
    accumulator's module is not a step)."""
    us = 1000
    data = {
        "window": (0, 100 * us),
        "spans": [("serve.put", 1 * us, 2 * us, 0),
                  ("serve.call", 3 * us, 40 * us, 0),
                  ("runner.step", 3 * us, 6 * us, None),
                  ("serve.block", 6 * us, 40 * us, None),
                  ("serve.put", 41 * us, 43 * us, 1),
                  ("serve.call", 44 * us, 90 * us, 1),
                  ("serve.block", 50 * us, 90 * us, None),
                  ("serve.call", 120 * us, 130 * us, 2)],   # past the end
        "bench": [],
        "devices": {0: {
            "modules": [("jit_tilt_sparse_steady(1)", 5 * us, 38 * us),
                        ("jit_tilt_obs_accum(2)", 38 * us, 39 * us),
                        ("jit_tilt_sparse_steady(1)", 47 * us, 85 * us)],
            "ops": [("%f", 5 * us, 38 * us,
                     "jit(tilt_sparse_steady)/tilt.gather/x", 1),
                    ("%g", 38 * us, 39 * us, "jit(tilt_obs_accum)/y", 2)]}}}
    assert program_trace.host_call_s(data) == pytest.approx(
        [(1 + 37 - 34) * 1e-6, (2 + 46 - 40) * 1e-6])
    assert program_trace.completion_lags(data) == pytest.approx(
        [2e-6, 5e-6])
    ph = program_trace.phase_s(data)
    assert ph == pytest.approx({"tilt.gather": 33e-6, None: 1e-6})
    sparse = re.compile(r"^jit_tilt_sparse_")
    assert program_trace.phase_s(data, sparse) == pytest.approx(
        {"tilt.gather": 33e-6})
    # 33 of the sparse modules' 71 us are under a phase
    assert program_trace.cover(data) == pytest.approx(33 / 71)


QUIET = os.path.join(DATA, "fraud_quiet_small.xplane.pb")


def test_recorded_sparse_trace(monkeypatch, tmp_path):
    """A 0.9 s traced window of fraud.quiet on one TPU v5e (three chunks,
    the metadata plane dropped): every phase reads a positive device time
    per chunk, the phases cover the sparse step's program, and the
    per-chunk host readings are there."""
    cell = tmp_path / "trace" / "fraud.quiet"
    cell.mkdir(parents=True)
    shutil.copy(QUIET, cell / "q.xplane.pb")
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    ctx = _ctx("fraud.quiet", n_chunks=3)
    got = {name: run.reader(name).read(ctx) for name in NEW_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the unit-window gather is most of the step (PR 12's reading)
    assert 200 < got["gather_ms"] < 260
    data = program_trace.load(QUIET)
    assert program_trace.cover(data) >= 0.95
    modules = {n.partition("(")[0] for n, _, _ in
               data["devices"][0]["modules"]}
    assert modules == {"jit_tilt_sparse_steady", "jit_tilt_obs_accum"}
    phases = program_trace.phase_s(data)
    assert {"tilt.change_detect", "tilt.compact", "tilt.gather",
            "tilt.compute", "tilt.scatter", "tilt.hold"} <= set(phases)
    # the phases' self time adds up to the device's busy time
    s = trace.summarize(QUIET, [0])
    assert sum(phases.values()) == pytest.approx(s["busy_s"], rel=1e-3)
    assert len(program_trace.completion_lags(data)) == 3
