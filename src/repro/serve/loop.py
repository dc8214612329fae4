"""The serving loop: AOT-warmed runner + double-buffered async data path
+ ring-buffer event admission.

:class:`ServeLoop` wraps one :class:`repro.engine.Runner`:

* :meth:`warm` AOT-prepares every staged step (:mod:`repro.serve.aot`) —
  loaded from the persisted executable cache when warm, compiled and
  persisted when cold.
* :meth:`serve` is the chunk path: chunk k+1's ``jax.device_put``
  (committed, non-blocking) is issued *before* chunk k's compute
  dispatch, so the H2D transfer of the next request overlaps the current
  step.  Every transfer on the steady-state path is explicit, so the
  whole loop runs under ``jax.transfer_guard("disallow")`` (pinned in
  tests/test_serve.py); the staged step's donation contract recycles the
  carried state buffers in place.
* :meth:`attach_events` + :meth:`offer` / :meth:`pump` is the event
  path: a fixed-capacity :class:`repro.serve.ring.AdmissionRing` feeds
  the disorder-tolerant :class:`repro.ingest.IngestRunner` (watermarks
  and lateness policies compose unchanged), with the same staged-put
  double buffering applied to sealed chunk batches and
  admission→result latency observed per sealed chunk.

:func:`build_service` is the one-call constructor that wires the
persisted caches: plan artifacts by structural fingerprint
(:class:`repro.multiquery.SharedPlanCache`) + serialized executables
(:class:`repro.serve.aot.ExecutableCache`).  A fresh process whose
caches are warm reaches first-result with zero planning, zero tracing
and zero compiles.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Iterable, Optional

import jax

from ..core import compile as qc
from ..core import ir
from ..core.stream import SnapshotGrid
from ..engine import ExecPolicy, Runner
from ..engine.runner import BodySpec
from ..ingest import IngestRunner
from ..multiquery import SharedPlanCache
from ..obs import Metrics, log_buckets
from .aot import ExecutableCache, aot_compile, step_fingerprint
from .ring import AdmissionRing

__all__ = ["ServeLoop", "build_service", "plan_artifact_of",
           "body_spec_from_artifact"]

_tm = jax.tree_util.tree_map


def plan_artifact_of(runner: Runner) -> Dict:
    """The pure-data planning artifact of a runner's body — everything a
    warm process needs to rebuild an equivalent :class:`BodySpec` without
    planning: per-input halo contracts, output geometry, the ChangePlan,
    and the φ seed shapes (so even the ``eval_shape`` trace is skipped).
    All plain dataclasses / ShapeDtypeStructs — pickles stably."""
    spec = runner.spec
    return {"input_specs": dict(spec.input_specs),
            "out_len": spec.out_len, "out_prec": spec.out_prec,
            "out_precs": dict(spec.out_precs),
            "change_plan": spec.change_plan, "solo": spec.solo,
            "seed_shapes": runner.seed_shape_spec()}


def body_spec_from_artifact(art: Dict) -> BodySpec:
    """A :class:`BodySpec` rebuilt from a persisted plan artifact.  The
    body is AOT-only: ``outs_fn`` raises if anything tries to trace it —
    with every staged step pre-installed from the executable cache it is
    never called, and a cache miss falls back to a real compile in
    :func:`build_service` instead of reaching this."""

    def outs_fn(inputs):
        raise RuntimeError(
            "AOT-only body: outs_fn rebuilt from a persisted plan artifact "
            "cannot be traced — serve from the executable cache, or "
            "rebuild with compile_query for a traceable body")

    return BodySpec(
        input_specs=dict(art["input_specs"]), out_len=art["out_len"],
        out_prec=art["out_prec"], outs_fn=outs_fn,
        out_precs=dict(art["out_precs"]), change_plan=art["change_plan"],
        root=None, jit=True, solo=art["solo"], step_cache={}, roots=())


class ServeLoop:
    """One served runner: AOT lifecycle + double-buffered chunk path +
    ring-admitted event path.  ``serve.*`` telemetry lands on the
    runner's metrics registry."""

    def __init__(self, runner: Runner, *,
                 exec_cache: Optional[ExecutableCache] = None,
                 query_fp: Optional[str] = None, device=None):
        self.runner = runner
        self.exec_cache = exec_cache
        self.query_fp = query_fp
        # local placement: commit chunks to the device ahead of dispatch
        # (the double buffer); mesh placement keeps the runner's own
        # sharded ingest placement
        self._device = (None if runner.policy.mesh is not None
                        else (device if device is not None
                              else jax.devices()[0]))
        m = self.metrics = runner.metrics
        self._tracer = m.tracer
        self._seq = 0  # chunk sequence number: the spans' ``chunk`` id
        self._m_call = m.histogram(
            "serve.call_seconds", log_buckets(1e-5, 10.0, per_decade=3),
            "end-to-end per-call serving latency (dispatch + device "
            "completion)", "s", log_scale=True)
        self._m_admit = m.histogram(
            "serve.admit_to_result_seconds",
            log_buckets(1e-5, 100.0, per_decade=2),
            "ring admission to sealed-result latency", "s", log_scale=True)
        self._m_first = m.gauge(
            "serve.first_result_seconds",
            "construction to first blocked result", "s")
        self._t_created = time.perf_counter()
        self._first_done = False
        self.aot_report: Dict[str, str] = {}
        self.ring: Optional[AdmissionRing] = None
        self.ingest: Optional[IngestRunner] = None
        self._admits: Dict[int, list] = {}

    # -- AOT lifecycle -------------------------------------------------------
    def warm(self, chunks: Optional[Dict] = None) -> Dict[str, str]:
        """AOT-prepare every staged step (load-or-compile+persist);
        returns ``{label: "loaded"|"compiled"}``."""
        self.aot_report = aot_compile(self.runner, self.exec_cache,
                                      chunks=chunks, query_fp=self.query_fp)
        return self.aot_report

    # -- chunk path ----------------------------------------------------------
    def _put(self, chunks: Dict[str, SnapshotGrid], **ids
             ) -> Dict[str, SnapshotGrid]:
        """Commit one request's grids to the serving device — an explicit
        (transfer-guard-legal) non-blocking H2D; issued for chunk k+1
        before chunk k's compute dispatch so the transfer overlaps.  The
        ``serve.put`` span times the host's enqueue, not the transfer."""
        if self._device is None:
            return chunks
        d = self._device
        with self._tracer.span("serve.put", **ids):
            return {name: SnapshotGrid(
                        value=_tm(lambda x: jax.device_put(x, d), g.value),
                        valid=jax.device_put(g.valid, d), t0=g.t0,
                        prec=g.prec)
                    for name, g in chunks.items()}

    def _put_next(self, chunks: Dict[str, SnapshotGrid]) -> tuple:
        """``(chunk id, staged grids)``: the put of the next chunk of the
        chunk path, numbered in the order chunks are put."""
        seq, self._seq = self._seq, self._seq + 1
        return seq, self._put(chunks, chunk=seq)

    def _call(self, seq: int, staged, block: bool):
        """One chunk's step (and, with ``block``, the wait for its result)
        under the ``serve.call`` span, whose duration is the
        ``serve.call_seconds`` observation."""
        with self._tracer.span("serve.call", chunk=seq) as took:
            out = self.runner.step(staged)
            if block:
                self._block(out)
        self._observe(took.seconds)
        return out

    def _block(self, out):
        with self._tracer.span("serve.block"):
            for g in (out.values() if isinstance(out, dict) else (out,)):
                jax.block_until_ready(g.valid)
        return out

    def _observe(self, dt: float) -> None:
        if self.metrics.on:
            self._m_call.observe(dt)
            if not self._first_done:
                self._first_done = True
                self._m_first.set(time.perf_counter() - self._t_created)

    def step(self, chunks: Dict[str, SnapshotGrid], *, block: bool = True):
        """Serve one chunk (single-shot path: no lookahead to overlap)."""
        return self._call(*self._put_next(chunks), block)

    def serve(self, chunk_source: Iterable[Dict[str, SnapshotGrid]], *,
              block: bool = True):
        """Generator over results, double-buffered: while chunk k
        computes (and the caller consumes its result), chunk k+1's
        buffers are already transferring.  With ``block`` (default) each
        yield is a completed device result and ``serve.call_seconds``
        measures honest end-to-end latency; ``block=False`` pipelines
        dispatch-deep and the caller owns synchronization."""
        it = iter(chunk_source)
        try:
            cur = self._put_next(next(it))
        except StopIteration:
            return
        live = True
        while live:
            try:
                nxt = self._put_next(next(it))  # k+1's H2D overlaps k's
            except StopIteration:
                nxt, live = None, False
            yield self._call(*cur, block)
            cur = nxt

    # -- event path ----------------------------------------------------------
    def attach_events(self, *, lateness: int, policy: str = "revise",
                      capacity: int = 1024, shed: str = "newest",
                      horizon_chunks: Optional[int] = None,
                      watermark_keys=None) -> None:
        """Wire the event front end: a bounded admission ring feeding a
        disorder-tolerant :class:`IngestRunner` whose chunk execution
        goes through the same staged-put double buffer."""
        self.ring = AdmissionRing(capacity, shed=shed, metrics=self.metrics)
        self.ingest = IngestRunner(
            self.runner, lateness=lateness, policy=policy,
            horizon_chunks=horizon_chunks, watermark_keys=watermark_keys,
            stage=self._put)

    def _need_events(self):
        if self.ingest is None:
            raise RuntimeError(
                "event path not attached (call attach_events first)")

    def offer(self, name: str, ev, key: int = 0) -> bool:
        """Admit one event into the ring (False = shed)."""
        self._need_events()
        return self.ring.offer(name, ev, key=key)

    def heartbeat(self, t: int) -> None:
        self._need_events()
        self.ingest.heartbeat(t)

    def _observe_sealed(self, sealed) -> None:
        if not sealed or not self.metrics.on:
            return
        now = time.perf_counter()
        for sc in sealed:
            for t in self._admits.pop(sc.chunk, ()):
                self._m_admit.observe(now - t)

    def pump(self, max_events: Optional[int] = None) -> tuple:
        """Drain the ring into the ingest front end (FIFO) and seal +
        execute every watermark-passed chunk.  Returns
        ``(sealed, corrections)`` like :meth:`IngestRunner.poll`."""
        self._need_events()
        span = self.ingest.chunk_span
        for e in self.ring.drain(max_events):
            self.ingest.push(e.name, e.event, key=e.key)
            self._admits.setdefault(
                (e.event.end - 1) // span, []).append(e.t_admit)
        sealed, corrections = self.ingest.poll()
        self._observe_sealed(sealed)
        return sealed, corrections

    def finish(self) -> tuple:
        """End of stream: drain everything, flush the ingest front end."""
        self._need_events()
        span = self.ingest.chunk_span
        for e in self.ring.drain():
            self.ingest.push(e.name, e.event, key=e.key)
            self._admits.setdefault(
                (e.event.end - 1) // span, []).append(e.t_admit)
        sealed, corrections = self.ingest.flush()
        self._observe_sealed(sealed)
        return sealed, corrections


def build_service(query, *, out_len: int,
                  policy: Optional[ExecPolicy] = None,
                  n_keys: Optional[int] = None, segs_per_chunk: int = 1,
                  cache_dir: Optional[str] = None,
                  metrics: Optional[Metrics] = None) -> ServeLoop:
    """Build a warmed :class:`ServeLoop` for one query.

    With ``cache_dir`` the two persisted caches live under it:
    ``plans.pkl`` (plan artifacts by structural fingerprint — the
    cross-session :class:`SharedPlanCache`) and ``aot/`` (serialized step
    executables).  First process: compile, plan, AOT-compile, persist.
    Fresh process, warm caches: the runner is rebuilt from the plan
    artifact (no planning), seeds are primed from persisted shapes (no
    tracing) and every staged step loads from disk (no compiling) —
    ``loop.plan_source == "warm"`` and the tracer's compile record stays
    empty.  Any cache miss falls back to the cold path transparently.

    jax's own persistent compilation cache is process-wide and is not
    touched here: it is on where ``JAX_COMPILATION_CACHE_DIR`` is set, or
    after :func:`repro.serve.enable_jax_compilation_cache`.

    The query compiles with the backend's kernel choice
    (:func:`repro.kernels.ops.use_pallas`): the Pallas kernels on TPU.
    """
    node = getattr(query, "node", query)
    policy = policy if policy is not None else ExecPolicy(body="sparse")
    if policy.union:
        raise NotImplementedError(
            "build_service serves solo queries; build a union BodySpec "
            "runner and wrap it in ServeLoop directly")
    plan_cache = SharedPlanCache(
        persist=os.path.join(cache_dir, "plans.pkl") if cache_dir else None)
    exec_cache = (ExecutableCache(os.path.join(cache_dir, "aot"))
                  if cache_dir else None)
    root = plan_cache.intern(node)
    fp = ir.fingerprint(root)

    runner, how = None, "cold"
    art = plan_cache.plan_artifact(fp, out_len)
    if (art is not None and exec_cache is not None and art["solo"]
            and (not policy.sparse or art["change_plan"] is not None)):
        r = Runner(body_spec_from_artifact(art), policy, n_keys=n_keys,
                   segs_per_chunk=segs_per_chunk, metrics=metrics)
        r.prime_seed_shapes(art.get("seed_shapes"))
        if all(exec_cache.has(step_fingerprint(r, label, query_fp=fp))
               for label, _ in r.aot_keys()):
            runner, how = r, "warm"
    if runner is None:
        exe = qc.compile_query(root, out_len=out_len, sparse=policy.sparse)
        runner = Runner(exe, policy, n_keys=n_keys,
                        segs_per_chunk=segs_per_chunk, metrics=metrics)
        plan_cache.store_artifact(fp, out_len, plan_artifact_of(runner))

    loop = ServeLoop(runner, exec_cache=exec_cache, query_fp=fp)
    loop.plan_source = how
    loop.warm()
    return loop
