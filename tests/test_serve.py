"""Low-latency serving (ISSUE 10): AOT-compiled policy steps, persisted
warm start, double-buffered async ingestion.

The headline invariants:

* **AOT equivalence** — a runner whose staged steps are AOT-lowered and
  installed (``repro.serve.aot_compile``) produces bit-identical outputs
  to the plain lazy-jit runner on the same chunk sequence.
* **Warm start is compile-free** — a second service built over the same
  cache directory rebuilds the runner from the persisted plan artifact
  and loads every step executable from disk: ``plan_source == "warm"``,
  the tracer records **zero** compiles, and outputs stay bit-identical
  (the executable round-trip through
  ``jax.experimental.serialize_executable`` preserves semantics and the
  donation contract).
* **Transfer-guard-clean steady state** — after the first two calls, the
  double-buffered chunk path runs entirely under
  ``jax.transfer_guard("disallow")``: the only H2D is the loop's own
  explicit committed ``device_put``.
* **Admission ring properties** — FIFO order preserved under every shed
  policy, depth bounded by capacity, offered == admitted + shed,
  ``shed='block'`` raises :class:`Backpressure`.
* **Event path** — ring-admitted bursty arrival through the
  :class:`IngestRunner` keeps the watermark monotone and seals chunks in
  order.
* The ``serving`` analysis pass certifies a fully-AOT runner and flags a
  missing executable / empty steady-state donation.
* ``launch/serve.py`` compiles prefill exactly once per run (the fixed
  recompile-per-wave bug).
"""
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.audit import audit_runner
from repro.analysis.passes import pass_serving
from repro.core import compile as qc
from repro.core.frontend import TStream
from repro.core.stream import Event, SnapshotGrid
from repro.engine import ExecPolicy, Runner
from repro.serve import (AdmissionRing, Backpressure, ExecutableCache,
                         aot_compile, build_service)

SEG = 8          # out_len of the served runners
SPC = 2          # segments per chunk
SPAN = SEG * SPC
WIN = 8
N_CHUNKS = 5


def _query():
    s = TStream.source("in", prec=1)
    mu = s.window(WIN).mean().shift(1)
    sd = s.window(WIN).stddev().shift(1)
    thr = mu.join(sd, lambda m, d: m + 3.0 * d)
    return s.join(thr, lambda x, t: x - t).where(lambda e: e > 0)


def _chunks(n, seed=5, span=SPAN, host=True):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        v = rng.integers(0, 100, span).astype(np.float32)
        m = np.ones(span, bool)
        if not host:
            v, m = jnp.asarray(v), jnp.asarray(m)
        out.append({"in": SnapshotGrid(value=v, valid=m, t0=i * span,
                                       prec=1)})
    return out


def _np(out):
    return np.asarray(out.value), np.asarray(out.valid)


# ---------------------------------------------------------------------------
# AOT compilation
# ---------------------------------------------------------------------------

def test_aot_outputs_bit_identical():
    """AOT-installed executables are the same computation: chunk-by-chunk
    outputs match the lazy-jit runner exactly."""
    exe = qc.compile_query(_query().node, out_len=SEG, pallas=False,
                           sparse=True)
    r_ref = Runner(exe, ExecPolicy(body="sparse"), segs_per_chunk=SPC)
    r_aot = Runner(exe, ExecPolicy(body="sparse"), segs_per_chunk=SPC)
    report = aot_compile(r_aot)
    assert report and all(v == "compiled" for v in report.values())
    assert {label for label, _ in r_aot.aot_keys()} == set(report)
    for c in _chunks(N_CHUNKS, host=False):
        v0, m0 = _np(r_ref.step(c))
        v1, m1 = _np(r_aot.step(c))
        np.testing.assert_array_equal(m0, m1)
        np.testing.assert_array_equal(v0[m0], v1[m1])


def test_executable_cache_roundtrip_and_corruption(tmp_path):
    """Store → has → load round-trips (meta included); a torn entry
    degrades to a miss and is removed, never an error."""
    exe = qc.compile_query(_query().node, out_len=SEG, pallas=False,
                           sparse=True)
    r = Runner(exe, ExecPolicy(body="sparse"), segs_per_chunk=SPC)
    cache = ExecutableCache(str(tmp_path))
    aot_compile(r, cache)
    fps = [f[:-5] for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert len(fps) == len(r.aot_keys())
    got = cache.load(fps[0])
    assert got is not None and isinstance(got[1], dict)
    # corrupt one entry: load misses, removes the file, and the next
    # aot_compile recompiles it rather than erroring
    with open(cache._file(fps[0]), "wb") as f:
        f.write(b"not a pickle")
    assert cache.load(fps[0]) is None
    assert not os.path.exists(cache._file(fps[0]))
    assert cache.load("missing-fingerprint") is None


# ---------------------------------------------------------------------------
# persisted warm start
# ---------------------------------------------------------------------------

def test_warm_start_zero_compiles_bit_identical(tmp_path):
    """The acceptance invariant: a fresh service over a warm cache
    directory plans nothing, traces nothing and compiles nothing — and
    still computes the same bits."""
    cache = str(tmp_path / "svc")
    svc1 = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                         cache_dir=cache)
    assert svc1.plan_source == "cold"
    outs1 = [_np(o) for o in svc1.serve(iter(_chunks(N_CHUNKS)))]

    svc2 = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                         cache_dir=cache)
    assert svc2.plan_source == "warm"
    assert all(v == "loaded" for v in svc2.aot_report.values())
    tracer = svc2.runner.metrics.tracer
    assert tracer.compiles() == {}, tracer.compiles()
    assert tracer.retraces() == {}, tracer.retraces()
    outs2 = [_np(o) for o in svc2.serve(iter(_chunks(N_CHUNKS)))]
    # still zero compiles after actually serving
    assert tracer.compiles() == {}, tracer.compiles()
    assert len(outs1) == len(outs2) == N_CHUNKS
    for (v1, m1), (v2, m2) in zip(outs1, outs2):
        np.testing.assert_array_equal(m1, m2)
        np.testing.assert_array_equal(v1[m1], v2[m2])


def test_warm_start_survives_missing_executable(tmp_path):
    """Deleting one persisted executable demotes the whole service to the
    cold path (plan may still be reused) — transparently, no error."""
    cache = str(tmp_path / "svc")
    build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                  cache_dir=cache)
    aot_dir = os.path.join(cache, "aot")
    victims = [f for f in os.listdir(aot_dir) if f.endswith(".aotx")]
    os.remove(os.path.join(aot_dir, victims[0]))
    svc = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                        cache_dir=cache)
    assert svc.plan_source == "cold"
    out = svc.step(_chunks(1)[0])
    assert np.asarray(out.valid).shape == (SPAN,)


def test_source_version_follows_every_staged_source(tmp_path):
    """The staged-code version hashes each package's sources by path and
    content: an edit, a new file or a rename moves it."""
    from repro.serve.aot import source_version
    pkg = tmp_path / "engine"
    pkg.mkdir()
    (pkg / "runner.py").write_text("def step(x):\n    return x\n")
    v0 = source_version(tmp_path, ["engine"])
    assert source_version(tmp_path, ["engine"]) == v0
    (pkg / "runner.py").write_text("def step(x):\n    return x + 1\n")
    v1 = source_version(tmp_path, ["engine"])
    (pkg / "extra.py").write_text("")
    v2 = source_version(tmp_path, ["engine"])
    (pkg / "extra.py").rename(pkg / "other.py")
    v3 = source_version(tmp_path, ["engine"])
    assert len({v0, v1, v2, v3}) == 4


def test_executable_from_other_staged_code_misses(tmp_path, monkeypatch):
    """An executable persisted by other step code is never loaded: the
    fingerprint carries the staged code's version, so a warm cache
    written before an edit to a step body demotes to the cold path and
    recompiles."""
    from repro.serve import aot
    assert aot.staged_code_version() == aot.staged_code_version()
    cache = str(tmp_path / "svc")
    svc1 = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                         cache_dir=cache)
    fp1 = aot.step_fingerprint(svc1.runner, "sparse_fused(steady)",
                               query_fp=svc1.query_fp)
    monkeypatch.setattr(aot, "staged_code_version", lambda: "edited")
    assert aot.step_fingerprint(svc1.runner, "sparse_fused(steady)",
                                query_fp=svc1.query_fp) != fp1
    svc2 = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                         cache_dir=cache)
    assert svc2.plan_source == "cold"
    assert set(svc2.aot_report.values()) == {"compiled"}


def test_plan_artifact_persists_across_cache_instances(tmp_path):
    from repro.core import ir
    from repro.multiquery import SharedPlanCache
    path = str(tmp_path / "plans.pkl")
    c1 = SharedPlanCache(persist=path)
    root = c1.intern(_query().node)
    fp = ir.fingerprint(root)
    c1.store_artifact(fp, SEG, {"solo": True, "probe": 7})
    c2 = SharedPlanCache(persist=path)
    assert c2.plan_artifact(fp, SEG) == {"solo": True, "probe": 7}
    assert c2.plan_artifact(fp, SEG + 1) is None
    # a torn store degrades to empty, never an error
    with open(path, "wb") as f:
        f.write(b"\x80garbage")
    assert SharedPlanCache(persist=path).plan_artifact(fp, SEG) is None


# ---------------------------------------------------------------------------
# double-buffered chunk path
# ---------------------------------------------------------------------------

def test_steady_state_is_transfer_guard_clean(tmp_path):
    """After warm-up, the serving generator runs under
    ``jax.transfer_guard("disallow")``: every H2D on the steady path is
    the loop's own explicit committed device_put."""
    svc = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                        cache_dir=str(tmp_path / "svc"))
    gen = svc.serve(iter(_chunks(8)))
    next(gen)
    next(gen)
    with jax.transfer_guard("disallow"):
        served = sum(1 for _ in gen)
    assert served == 6
    snap = svc.runner.metrics.snapshot()
    assert snap["histograms"]["serve.call_seconds"]["count"] == 8
    assert snap["gauges"]["serve.first_result_seconds"]["value"] > 0


# ---------------------------------------------------------------------------
# admission ring
# ---------------------------------------------------------------------------

def _ev(i):
    return Event(i, i + 1, float(i))


def test_ring_fifo_and_tail_drop():
    ring = AdmissionRing(4, shed="newest")
    assert [ring.offer("in", _ev(i)) for i in range(6)] == [True] * 4 + \
        [False] * 2
    assert ring.depth == 4
    drained = ring.drain()
    assert [e.event.start for e in drained] == [0, 1, 2, 3]  # FIFO
    assert [e.t_admit for e in drained] == sorted(e.t_admit
                                                 for e in drained)
    snap = ring.metrics.snapshot()
    assert snap["counters"]["serve.admitted"]["value"] == 4
    assert snap["counters"]["serve.shed_events"]["value"] == 2
    assert snap["gauges"]["serve.ring_capacity"]["value"] == 4


def test_ring_oldest_evicts_head():
    ring = AdmissionRing(3, shed="oldest")
    assert all(ring.offer("in", _ev(i)) for i in range(5))  # always admits
    assert [e.event.start for e in ring.drain()] == [2, 3, 4]
    snap = ring.metrics.snapshot()
    assert snap["counters"]["serve.shed_events"]["value"] == 2


def test_ring_block_raises_backpressure():
    ring = AdmissionRing(2, shed="block")
    ring.offer("in", _ev(0))
    ring.offer("in", _ev(1))
    with pytest.raises(Backpressure):
        ring.offer("in", _ev(2))
    ring.drain(1)
    assert ring.offer("in", _ev(2))  # room again after a drain


def test_ring_property_bursty_random():
    """Randomized offers/drains against a plain-list model: FIFO order,
    bounded depth, offered == admitted + shed — under bursty arrival."""
    rng = np.random.default_rng(42)
    ring = AdmissionRing(8, shed="newest")
    model, drained, offered, admitted = [], [], 0, 0
    for _ in range(200):
        if rng.random() < 0.6:  # bursty: offer in runs
            for _ in range(int(rng.integers(1, 6))):
                ev = _ev(offered)
                offered += 1
                ok = ring.offer("in", ev)
                assert ok == (len(model) < 8)
                if ok:
                    model.append(ev)
                    admitted += 1
        else:
            k = int(rng.integers(1, 6))
            got = ring.drain(k)
            assert [e.event for e in got] == model[:len(got)]
            drained += [e.event.start for e in got]
            del model[:len(got)]
        assert ring.depth == len(model) <= 8
    snap = ring.metrics.snapshot()
    assert snap["counters"]["serve.admitted"]["value"] == admitted
    assert (snap["counters"]["serve.shed_events"]["value"]
            == offered - admitted)
    assert drained == sorted(drained)  # global FIFO across bursts


def test_ring_rejects_bad_args():
    with pytest.raises(ValueError):
        AdmissionRing(0)
    with pytest.raises(ValueError):
        AdmissionRing(4, shed="spill")


# ---------------------------------------------------------------------------
# event path: ring -> ingest, watermark monotone under bursty arrival
# ---------------------------------------------------------------------------

def test_event_path_watermark_monotone_bursty(tmp_path):
    svc = build_service(_query(), out_len=SEG, segs_per_chunk=SPC,
                        cache_dir=str(tmp_path / "svc"))
    svc.attach_events(lateness=8, policy="drop", capacity=1024)
    T = SPAN * 6
    rng = np.random.default_rng(9)
    events = [Event(t, t + 1, float(rng.integers(0, 100)))
              for t in range(T)]
    # bounded-disorder bursty arrival: sort by start + jitter < lateness
    jit = rng.integers(0, 8, size=T)
    order = np.argsort([e.start + j for e, j in zip(events, jit)],
                       kind="stable")
    wms, sealed_chunks = [], []
    for burst in np.array_split(order, 10):
        for i in burst:
            assert svc.offer("in", events[i])
        sealed, _ = svc.pump()
        sealed_chunks += [s.chunk for s in sealed]
        wms.append(svc.ingest.tracker.watermark)
    sealed, _ = svc.finish()
    sealed_chunks += [s.chunk for s in sealed]
    # watermark never regresses, chunks seal in order, stream covered
    assert all(a <= b for a, b in zip(wms, wms[1:])), wms
    assert sealed_chunks == sorted(sealed_chunks)
    assert sealed_chunks == list(range(6))
    snap = svc.runner.metrics.snapshot()
    assert snap["counters"]["serve.admitted"]["value"] == T
    assert (snap["histograms"]["serve.admit_to_result_seconds"]["count"]
            > 0)


# ---------------------------------------------------------------------------
# the serving analysis pass
# ---------------------------------------------------------------------------

def _aot_runner():
    exe = qc.compile_query(_query().node, out_len=SEG, pallas=False,
                           sparse=True)
    r = Runner(exe, ExecPolicy(body="sparse"), segs_per_chunk=SPC)
    aot_compile(r)
    return r


def test_pass_serving_certifies_aot_runner():
    r = _aot_runner()
    findings = audit_runner(r, passes={"serving": pass_serving})
    assert [f.code for f in findings] == ["serving-aot-complete"], findings


def test_pass_serving_flags_missing_step_and_donation():
    r = _aot_runner()
    # a step reachable by the policy point but never AOT-installed (the
    # real-world shape: a variant enabled after warm()) -> error
    label, key = r.aot_keys()[0]
    del r.aot_record[key]
    findings = audit_runner(r, passes={"serving": pass_serving})
    assert any(f.code == "serving-step-not-aot" and f.severity == "error"
               for f in findings), findings
    # empty steady-state donation contract -> error
    r2 = _aot_runner()
    steady = [k for la, k in r2.aot_keys()
              if la in ("sparse_fused(steady)", "dense")]
    assert steady
    r2.aot_record[steady[0]]["donate"] = ()
    findings = audit_runner(r2, passes={"serving": pass_serving})
    assert any(f.code == "serving-donation-missing"
               and f.severity == "error" for f in findings), findings


def test_pass_serving_noop_on_unserved_runner():
    exe = qc.compile_query(_query().node, out_len=SEG, pallas=False,
                           sparse=True)
    r = Runner(exe, ExecPolicy(body="sparse"), segs_per_chunk=SPC)
    assert audit_runner(r, passes={"serving": pass_serving}) == []


# ---------------------------------------------------------------------------
# jax's persistent compilation cache: placed from outside, else one fixed path
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import os, jax, jax.numpy as jnp
from repro.serve import enable_jax_compilation_cache
from repro.serve.aot import DEFAULT_JAX_CACHE_DIR
path = enable_jax_compilation_cache()
assert path == jax.config.jax_compilation_cache_dir, path
want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_JAX_CACHE_DIR
assert path == want, (path, want)
if "JAX_COMPILATION_CACHE_DIR" in os.environ:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert os.listdir(path), "no cache entry written"
print("CACHE_AT", path)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_jax_cache_placed_from_outside_or_fixed(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiles land there and no
    other directory is set; without it the cache is the checkout's fixed
    ``out/jax_cache``, whatever the working directory."""
    import subprocess
    import sys
    from repro.serve.aot import DEFAULT_JAX_CACHE_DIR
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = src
    env.setdefault("JAX_PLATFORMS", "cpu")
    cwd = tmp_path / "elsewhere"
    cwd.mkdir()
    want = str(tmp_path / "cache") if from_env else DEFAULT_JAX_CACHE_DIR
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       cwd=cwd, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert f"CACHE_AT {want}" in p.stdout
    assert os.path.isabs(want) and not os.listdir(cwd)


# ---------------------------------------------------------------------------
# launch/serve.py: prefill compiled once per run
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_serve_prefill_compiles_once():
    from repro.configs.base import get_config
    from repro.launch.serve import _make_prefill
    from repro.models.model import build_model
    from repro.train.train_step import make_serve_steps
    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    prefill_fn, _ = make_serve_steps(model)
    prefill = _make_prefill(model, prefill_fn, cfg.family == "encdec", 12)
    tokens = jnp.zeros((2, 8), jnp.int32)
    prefill(params, tokens)
    prefill(params, tokens)  # second wave, same shapes: cache hit
    assert prefill._cache_size() == 1


@pytest.mark.slow
def test_launch_serve_main_continuous_batching():
    """More requests than batch slots: several waves through ONE hoisted
    prefill; every real request decodes to the full budget."""
    from repro.launch.serve import main
    done = main(["--arch", "qwen3-1.7b", "--smoke", "--batch", "2",
                 "--prompt-len", "8", "--gen", "4", "--requests", "5"])
    assert len(done) == 5
    assert all(len(seq) == 4 for seq in done)
