"""Serving latency: AOT-compiled steps, p50/p99 per call, cold vs warm.

The serving loop (:mod:`repro.serve`) attacks the two latencies the batch
benchmarks never see:

* **per-call tail latency** — every staged step is AOT-installed before
  the first request, so no request ever traces or compiles in-band, and
  chunk k+1's H2D transfer overlaps chunk k's compute (double buffer).
  We sweep the per-call batch (events per served chunk) over 1…1000 and
  report host-measured p50/p99 across a run of back-to-back calls, plus
  the tracer's compile/retrace record proving the steady state never
  recompiles.  Compare fig9: the partitioned one-shot path pays ~ms-scale
  dispatch per call at small batches; the served runner's AOT step keeps
  the p99 flat.

* **time-to-first-result** — a cold process pays plan + trace + XLA
  compile before result one; a warm process rebuilds the runner from the
  persisted plan artifact and loads serialized executables
  (``cold_first_result_s`` vs ``warm_first_result_s`` in the section
  config, measured at batch=100 with a fresh tmp cache dir: "cold" has
  no plan artifact and no AOT executables.  jax's own persistent
  compilation cache is not moved — it stays where
  ``JAX_COMPILATION_CACHE_DIR`` or the checkout's ``out/jax_cache`` puts
  it, so XLA compiles it already holds are reused by both runs).
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from repro.core.frontend import TStream
from repro.core.stream import SnapshotGrid
from repro.serve import build_service

from .common import row, set_config

BATCHES = (1, 10, 100, 1_000)
WINDOW = 16
WARMUP_CALLS = 2
FIRST_RESULT_BATCH = 100


def _fraud(win: int = WINDOW):
    s = TStream.source("in", prec=1)
    mu = s.window(win).mean().shift(1)
    sd = s.window(win).stddev().shift(1)
    thr = mu.join(sd, lambda m, d: m + 3.0 * d)
    return s.join(thr, lambda x, t: x - t).where(lambda e: e > 0)


def _chunks(span: int, n: int, seed: int = 5):
    # host numpy: the loop's explicit device_put is the only H2D
    rng = np.random.default_rng(seed)
    for i in range(n):
        v = rng.integers(0, 100, span).astype(np.float32)
        yield {"in": SnapshotGrid(value=v, valid=np.ones(span, bool),
                                  t0=i * span, prec=1)}


def _serve_calls(svc, span: int, calls: int):
    """Per-call wall seconds (blocked results) over ``calls`` requests
    through the double-buffered generator, warmup calls dropped; also the
    number of compiles recorded *during* the timed calls (the
    tracer-verified zero-per-request-recompile proof)."""
    tracer = svc.runner.metrics.tracer
    gen = svc.serve(_chunks(span, calls + WARMUP_CALLS))
    for _ in range(WARMUP_CALLS):
        next(gen)
    c0 = sum(tracer.compiles().values())
    dts = np.empty(calls)
    for j in range(calls):
        t0 = time.perf_counter()
        next(gen)
        dts[j] = time.perf_counter() - t0
    gen.close()
    return dts, sum(tracer.compiles().values()) - c0


def _first_result(cache_dir: str, batch: int) -> float:
    """Construction → first blocked result, one fresh service."""
    t0 = time.perf_counter()
    svc = build_service(_fraud(), out_len=batch, segs_per_chunk=1,
                        cache_dir=cache_dir)
    next(svc.serve(_chunks(batch, 1)))
    return time.perf_counter() - t0, svc


def run(n_events: int = 1_000_000):
    tmp = tempfile.mkdtemp(prefix="figlat_")
    try:
        p99_b100 = None
        for batch in BATCHES:
            calls = int(np.clip(n_events // (batch * 200), 10, 200))
            svc = build_service(_fraud(), out_len=batch, segs_per_chunk=1,
                                cache_dir=f"{tmp}/b{batch}")
            dts, steady_compiles = _serve_calls(svc, batch, calls)
            assert steady_compiles == 0, steady_compiles
            tracer = svc.runner.metrics.tracer
            p50, p99 = np.percentile(dts, (50, 99))
            if batch == FIRST_RESULT_BATCH:
                p99_b100 = p99
            row(f"figlat_serve_b{batch}", p99 * 1e6,
                f"{batch / p50 / 1e6:.3f}Mev/s,batch={batch},"
                f"p50_us={p50 * 1e6:.1f},p99_us={p99 * 1e6:.1f},"
                f"calls={calls},steady_compiles={steady_compiles},"
                f"retraces={sum(tracer.retraces().values())}",
                metrics=svc.runner.metrics)

        # cold vs warm first-result: same fresh cache dir twice, two
        # "processes" (fresh runner; the AOT cache dir starts empty)
        fr_dir = f"{tmp}/firstresult"
        t_cold, svc_c = _first_result(fr_dir, FIRST_RESULT_BATCH)
        assert svc_c.plan_source == "cold"
        t_warm, svc_w = _first_result(fr_dir, FIRST_RESULT_BATCH)
        assert svc_w.plan_source == "warm", svc_w.plan_source
        assert not svc_w.runner.metrics.tracer.compiles(), \
            svc_w.runner.metrics.tracer.compiles()
        row("figlat_first_result_cold", t_cold * 1e6,
            f"mode=cold,batch={FIRST_RESULT_BATCH},aot=compiled")
        row("figlat_first_result_warm", t_warm * 1e6,
            f"mode=warm,batch={FIRST_RESULT_BATCH},aot=loaded,"
            f"speedup={t_cold / t_warm:.1f}")
        set_config(window=WINDOW, warmup_calls=WARMUP_CALLS,
                   p99_batch100_us=round(float(p99_b100) * 1e6, 1),
                   cold_first_result_s=round(t_cold, 3),
                   warm_first_result_s=round(t_warm, 3),
                   warm_speedup=round(t_cold / t_warm, 1))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    run()
