"""One chunked runner for every execution policy (body × keys × placement ×
dag).

Every chunked executor in the stack — ``StreamRunner``,
``SparseStreamRunner``, ``KeyedEngine``, ``MultiQuerySession`` — used to
carry its own copy of the same machinery: concatenate carried halo tails
with the fresh chunk, stage a per-partition body, slice new tails off the
buffer, advance a stream clock, checkpoint it all.  :class:`Runner` owns
that machinery exactly once, parameterized by an
:class:`repro.engine.policy.ExecPolicy`; the old entry points are thin
deprecated wrappers over it.

Execution model (one ``step`` = one chunk):

* The chunk timeline is cut into ``segs_per_chunk`` **segments** of
  ``out_len`` output ticks each (one planned partition per segment).  Work
  units are ``keys × segments``; a dense body computes every unit, a sparse
  body only the units whose dilated input lineage saw a change
  (:class:`repro.core.plan.ChangePlan`), the rest *hold* their previous
  output (see :mod:`repro.core.sparse` for the semantics and exactness
  argument).
* ``keys='vmapped'`` adds a leading key axis to every grid; internally the
  runner always carries the key axis (``K=1`` for ``keys='single'``), so
  there is exactly one code path.
* ``placement=mesh(axis)`` shards the *work-unit* axis over the mesh: whole
  keys when keyed (buffers and carried state shard with them — no
  collectives, keys never communicate), segments when single-keyed (the
  chunk buffer is replicated).  Sparse compaction is **per shard**: each
  device resolves its local dirty units with a local ``nonzero`` into a
  per-shard power-of-two capacity bucket, so the gather never crosses
  devices — this is what lets sparse execution compose with mesh sharding
  (the global-gather limitation ``KeyedEngine(sparse=True)`` used to reject).
* ``dag='union'`` runs the union DAG of N queries (one
  :class:`repro.core.plan.UnionPlan`) and returns one grid per query; the
  merged :class:`~repro.core.plan.ChangePlan` of the union is the per-input
  union of the per-query dilations, so sparse execution composes with
  multi-query sharing too.

State pytree (the *only* cross-chunk state, host-roundtrippable through
:meth:`Runner.state` / :meth:`Runner.restore` with one validation path)::

    { input_name: (value_tail, valid_tail),   # trailing left_halo ticks
      "__t": int,                             # stream clock
      "__sparse": {                           # body='sparse' only
         "dirty": {input_name: dirty_tail},   # change flags for those ticks
         "prev":  {input_name: 1-tick snapshot},  # halo-free inputs only:
                                              # next chunk's tick 0 diffs
                                              # vs this (halo-carrying
                                              # inputs read the dirty tail)
         "seed":  {out_name: last output tick},   # hold seed per output
         "started": bool } }
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


from ..core import ir
from ..core import sparse as sparse_mod
from ..core.plan import ChangePlan, InputSpec, seg_range_affine
from ..core.stream import SnapshotGrid
from ..kernels import sparse_compact
from ..obs import Metrics, log_buckets
from .policy import ExecPolicy

__all__ = ["BodySpec", "Runner", "body_spec_of"]

_tm = jax.tree_util.tree_map


@dataclasses.dataclass
class BodySpec:
    """Everything the unified runner needs to know about a per-segment body.

    A body evaluates one planned partition: given ``{input_name: (value,
    valid)}`` grids covering one segment plus halo (``input_specs``), it
    returns ``{out_name: (value, valid)}`` output grids of ``span //
    out_precs[name]`` ticks each.  Solo queries are the single-output case
    (``out_name == "__out"``); union DAGs return one entry per query.

    ``step_cache`` holds the staged (traced + jitted) chunk steps, keyed by
    execution geometry — share it across Runner instances over the same
    compiled query so fresh runners (new stream epochs, benchmark repeats)
    reuse compiled executables.
    """

    input_specs: Dict[str, InputSpec]
    out_len: int     # segment length in ticks of the reference output grid
    out_prec: int
    outs_fn: Callable[[Dict[str, tuple]], Dict[str, tuple]]
    out_precs: Dict[str, int]
    change_plan: Optional[ChangePlan] = None
    root: Optional[ir.Node] = None
    jit: bool = True
    solo: bool = True
    step_cache: dict = dataclasses.field(default_factory=dict)
    # IR roots backing outs_fn, for static verification (repro.analysis):
    # solo bodies carry (root,); union bodies one root per query.  Empty
    # means the body is opaque (hand-built outs_fn) and the temporal-plan
    # verifier can only check internal plan consistency, not re-derive it.
    roots: tuple = ()

    @property
    def span(self) -> int:
        return self.out_len * self.out_prec


def body_spec_of(exe) -> BodySpec:
    """The :class:`BodySpec` of a :class:`repro.core.compile.CompiledQuery`
    (the ``dag='solo'`` case).  The step cache lives on the CompiledQuery,
    so every Runner over the same executable shares staged steps."""

    def outs_fn(inputs: Dict[str, tuple]) -> Dict[str, tuple]:
        return {"__out": exe.trace_fn(inputs)}

    return BodySpec(
        input_specs=exe.input_specs, out_len=exe.out_len,
        out_prec=exe.out_prec, outs_fn=outs_fn,
        out_precs={"__out": exe.out_prec},
        change_plan=getattr(exe, "change_plan", None), root=exe.root,
        jit=True, solo=True,
        step_cache=exe.__dict__.setdefault("_runner_step_cache", {}),
        roots=(exe.root,) if exe.root is not None else ())


def _bc(mask, x):
    """Broadcast a leading-axes mask over the trailing dims of ``x``."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))


def _unit_windows(x, core: int, length: int, seg0, ids=None, per_row=None):
    """Work units' halo windows from a buffer ``x`` (rows, ticks, …), as
    (units, length, …): unit (k, s) is the ``length`` ticks of row ``k``
    from tick ``(seg0 + s)·core`` — one contiguous slice per unit, never
    ``length`` scalar (row, tick) gathers (on a TPU v5e those take about
    a hundred times as long as slices of the same bytes).

    ``ids=None`` takes every unit, ``per_row`` segments of each row in
    row-major order (the full-capacity bucket): static slices of the
    rows, stacked, with no gather at all.  ``ids=(k_ids, s_ids)`` are
    traced unit ids (a compacted bucket's ``nonzero``): one gather of
    whole windows.  Every window starts on a ``core`` boundary, so the
    rows are cut into ``core``-tick blocks (zero-padded to a whole block)
    and each unit's slice is the ``ceil(length/core)`` blocks from block
    ``seg0 + s``, trimmed to ``length`` — a gather whose slice is the
    window, which the TPU runs natively, where a ``(1, length)``
    dynamic-slice gather lowers to a loop of one slice per unit."""
    rows_n, ticks = x.shape[:2]
    trail = x.shape[2:]
    if ids is None:
        span = (per_row - 1) * core + length
        rows = jax.lax.dynamic_slice_in_dim(x, seg0 * core, span, axis=1)
        win = jnp.stack([rows[:, j * core:j * core + length]
                         for j in range(per_row)], axis=1)
        return win.reshape((rows_n * per_row, length) + trail)
    k_ids, s_ids = ids
    n_blk, w_blk = -(-ticks // core), -(-length // core)
    pad = [(0, 0), (0, n_blk * core - ticks)] + [(0, 0)] * len(trail)
    blocks = jnp.pad(x, pad).reshape((rows_n, n_blk, core) + trail)
    zeros = (0,) * (1 + len(trail))

    def one(k, s):
        return jax.lax.dynamic_slice(
            blocks, (k, seg0 + s) + zeros, (1, w_blk, core) + trail)[0]

    win = jax.vmap(one)(k_ids, s_ids)
    win = win.reshape((win.shape[0], w_blk * core) + trail)
    return win[:, :length]


class Runner:
    """Chunked streaming execution under one :class:`ExecPolicy`.

    Parameters
    ----------
    exe_or_spec:
        A :class:`~repro.core.compile.CompiledQuery` (``dag='solo'``; pass
        ``sparse=True`` to :func:`~repro.core.compile.compile_query` for a
        sparse body) or a prebuilt :class:`BodySpec` (the union path —
        see :func:`repro.multiquery.union_runner`).
    policy:
        The execution policy.  ``keys='vmapped'`` requires ``n_keys``;
        ``placement=mesh`` shards keys (vmapped) or segments (single) and
        requires the respective count to divide the mesh axis size.
    segs_per_chunk:
        Segments consumed per :meth:`step`; each chunk supplies
        ``segs_per_chunk · spec.core`` fresh ticks per input.
    metrics:
        An :class:`repro.obs.Metrics` registry to accumulate runtime
        telemetry into (``runner.*`` metric names — see
        docs/architecture.md "Observability").  Default: a fresh private
        registry on ``self.metrics``.  Pass a shared registry to pool
        telemetry across runners (e.g. a session rebuilding its runner
        across attach/detach): device-resident accumulations of the
        previous owner are folded to host first, so nothing is lost.
    """

    def __init__(self, exe_or_spec, policy: ExecPolicy = ExecPolicy(), *,
                 n_keys: Optional[int] = None, segs_per_chunk: int = 1,
                 metrics: Optional[Metrics] = None):
        spec = (exe_or_spec if isinstance(exe_or_spec, BodySpec)
                else body_spec_of(exe_or_spec))
        if policy.union != (not spec.solo):
            raise ValueError(
                f"policy dag={policy.dag!r} does not match the body "
                f"(solo={spec.solo}); union runners need a union BodySpec "
                "(see repro.multiquery.union_runner)")
        if segs_per_chunk < 1:
            raise ValueError("segs_per_chunk must be >= 1")
        self.spec, self.policy = spec, policy
        self.n_segs = segs_per_chunk
        if policy.keyed:
            if n_keys is None:
                raise ValueError("keys='vmapped' needs n_keys")
            self.n_keys = n_keys
        else:
            if n_keys not in (None, 1):
                raise ValueError(
                    f"keys='single' runs one stream (got n_keys={n_keys}); "
                    "use ExecPolicy(keys='vmapped') for keyed sub-streams")
            self.n_keys = 1

        span = spec.span
        for name, s in spec.input_specs.items():
            if s.right_halo > 0:
                raise NotImplementedError(
                    "chunked runners support lookback-only queries "
                    f"(input {name} has lookahead)")
            if s.core * s.prec != span:
                raise ValueError(
                    f"input {name}: segment span {span} not a multiple of "
                    f"input precision {s.prec}")
        if policy.sparse and spec.change_plan is None:
            raise ValueError(
                "ExecPolicy(body='sparse') needs a query compiled with "
                "sparse=True (no ChangePlan attached)")
        if spec.root is not None and policy.keyed:
            keyed_inputs = [n.name for n in ir.free_inputs(spec.root)
                            if n.keyed]
            if keyed_inputs and set(keyed_inputs) != set(spec.input_specs):
                raise ValueError(
                    "query mixes keyed and unkeyed sources: "
                    f"keyed={keyed_inputs}, all={sorted(spec.input_specs)}")
        if policy.mesh is not None:
            n = policy.n_shards
            if policy.keyed and self.n_keys % n:
                raise ValueError(
                    f"n_keys={self.n_keys} not divisible by mesh axis "
                    f"'{policy.axis}' of size {n}")
            if not policy.keyed and self.n_segs % n:
                raise ValueError(
                    f"segs_per_chunk={self.n_segs} not divisible by mesh "
                    f"axis '{policy.axis}' of size {n}")

        # -- the unified state pytree ---------------------------------------
        self._tails: Dict[str, tuple] = {}
        self._sparse: Optional[dict] = (
            {"dirty": {}, "prev": {}, "seed": {}, "started": False}
            if policy.sparse else None)
        self._t = 0
        # -- sparse-body diagnostics (device-resident: reading them via
        # dirty_stats() syncs, accumulating them does not) ------------------
        self.last_seg_dirty = None
        self._dirty_units = None
        self._total_units = 0
        self._chunks_run = 0
        self._mstate = None  # (dirty_total, bucket_picks, frac_counts)
        # -- late-data revision ring (off unless enable_revision) -----------
        self._rev_ring: Optional[collections.deque] = None
        self.revision_horizon = 0
        self.revise_bound: Optional[int] = None
        # -- AOT serving record (populated by install_executable) -----------
        # staging key -> {"label", "how": "loaded"|"compiled", "donate"}:
        # the serving analysis pass reads this to prove every step a served
        # policy point dispatches is backed by an AOT executable
        self.aot_record: Dict[tuple, dict] = {}
        self._obs_init(metrics)

    # -- telemetry -----------------------------------------------------------
    def _obs_init(self, metrics: Optional[Metrics]) -> None:
        """Create/bind the runner's metric handles (see the metric-names
        reference in docs/architecture.md).  Device-resident metrics hold
        references into ``self._mstate``, the per-runner device
        accumulator state updated by one jitted dispatch per sparse chunk
        (:meth:`_obs_accum`); host metrics are plain Python arithmetic."""
        self.metrics = m = metrics if metrics is not None else Metrics()
        self._m_chunks = m.counter(
            "runner.chunks", "chunks stepped", "chunks")
        self._m_units = m.counter(
            "runner.units", "work units (keys x segments) presented",
            "units")
        self._m_keys = m.gauge("runner.keys", "keyed sub-streams", "keys")
        self._m_keys.set(self.n_keys)
        self._m_ingest_puts = m.counter(
            "runner.ingest_puts",
            "chunk leaves the step itself put on the mesh's ingest "
            "sharding (0 when the caller committed them ahead)", "leaves")
        self._m_donated = m.counter(
            "runner.donated_steps",
            "steps run through a buffer-donating jitted step", "steps")
        self._m_dispatches = m.counter(
            "runner.dispatches",
            "device programs launched (chunk step, metrics accumulator, "
            "revision steps)", "programs")
        self._m_lat = m.histogram(
            "runner.step_seconds", log_buckets(1e-5, 10.0, per_decade=3),
            "per-chunk step wall time (dispatch, not device completion)",
            "s", log_scale=True)
        self._m_rev_runs = m.counter(
            "runner.revision_runs", "late-data revision re-runs", "runs")
        self._m_rev_chunks = m.counter(
            "runner.revision_chunks",
            "sealed chunks re-stepped by revisions", "chunks")
        self._m_rev_units = m.counter(
            "runner.revision_units",
            "work units recomputed by revisions (ChangePlan-dilated dirty "
            "segments only)", "units")
        # device-resident handles: fold any previous owner's device refs
        # into the host base before this runner's mstate takes over
        self._m_dirty = m.counter(
            "runner.dirty_units", "work units that actually computed",
            "units")
        self._m_dirty.fold_device()
        ladder = (sparse_mod.capacity_ladder(self._U // self.policy.n_shards)
                  if self.policy.sparse else [])
        self._obs_caps = np.asarray(ladder, np.int32)
        if ladder:
            labels = [str(c) for c in ladder]
            prior = m.get("runner.bucket_picks")
            if prior is not None and prior.labels != labels:
                # a rebuilt runner at a new geometry has a new ladder —
                # the old slots don't mean anything anymore
                m.drop("runner.bucket_picks")
            self._m_picks = m.vector(
                "runner.bucket_picks", labels,
                "per-shard capacity-bucket selections (slot = capacity)",
                "picks")
            self._m_picks.fold_device()
        else:
            self._m_picks = None
        self._obs_frac_edges = np.linspace(1 / 16, 1.0, 16)
        self._m_frac = m.histogram(
            "runner.dirty_fraction", [round(float(e), 6)
                                      for e in self._obs_frac_edges],
            "per-chunk dirty work-unit fraction", "fraction")
        self._m_frac.fold_device()
        m.register_collector("runner", self._obs_collect)
        m.register_warmup_reset("runner", self._obs_warmup_reset)

    def _obs_warmup_reset(self) -> None:
        """Registry warmup-reset hook (:meth:`repro.obs.Metrics.
        reset_after_warmup`): re-base this runner's device accumulator and
        compaction window so long-lived services scope percentiles past
        the compiling first chunks.  The stream state itself (tails,
        clock, sparse change state) is untouched — only measurements
        reset.  The fresh mstate is created eagerly here (off the hot
        path) so the next chunk's accumulator dispatch stays
        transfer-free, and static gauges are re-asserted."""
        if self.policy.sparse:
            self._mstate = (jnp.zeros((), jnp.int32),
                            jnp.zeros((len(self._obs_caps),), jnp.int32),
                            jnp.zeros((len(self._obs_frac_edges) + 1,),
                                      jnp.int32))
        else:
            self._mstate = None
        self._dirty_units = None
        self._total_units = 0
        self._chunks_run = 0
        self._m_keys.set(self.n_keys)

    def _obs_collect(self) -> None:
        """Pre-snapshot hook: derived gauges (syncs — off the hot path)."""
        m = self.metrics
        entries = 0
        for f in self.spec.step_cache.values():
            size = getattr(f, "_cache_size", None)
            if callable(size):
                entries += size()
        # jax's own jit-cache entry count across this query's staged
        # steps: together with the tracer's per-key compile counts this
        # catches shape-driven retraces *inside* one staged step
        m.gauge("runner.jit_entries",
                "live jax jit-cache entries across staged steps").set(entries)
        stats = self.dirty_stats()
        if stats is not None:
            m.gauge("runner.compact",
                    "dirty fraction since construction/reset",
                    "fraction").set(stats["compact"])

    def _obs_accum(self):
        """The per-chunk device metric accumulator: ONE jitted dispatch
        folds every device-resident metric update (dirty total, per-shard
        bucket picks, dirty-fraction histogram) into the running mstate.
        Donates mstate, so the buffers update in place; the metric
        handles then just re-point at the new leaves (no dispatch, no
        transfer)."""
        key = self._cache_key("obs_accum")
        cache = self.spec.step_cache
        if key in cache:
            return cache[key]
        caps = self._obs_caps
        edges = self._obs_frac_edges
        U = self._U
        n_shards = self.policy.n_shards
        U_loc = U // n_shards

        def accum(mstate, seg_dirty):
            total, picks, frac = mstate
            # exact per-shard counts: the unit axis splits contiguously
            # over shards, so this mirrors the fused step's in-shard pick
            per_shard = seg_dirty.reshape(n_shards, U_loc).sum(
                axis=1, dtype=jnp.int32)
            cnt = per_shard.sum()
            b = jnp.clip(jnp.searchsorted(jnp.asarray(caps), per_shard,
                                          side="left"),
                         0, len(caps) - 1)
            f = cnt.astype(jnp.float32) / U
            fi = jnp.searchsorted(jnp.asarray(edges, jnp.float32), f,
                                  side="left")
            return (total + cnt,
                    picks.at[b].add(1),
                    frac.at[fi].add(1))

        self.metrics.tracer.record_compile(self._compile_label(key))
        return self._stage(key, accum, "tilt_obs_accum", donate=(0,))

    def _obs_sparse_chunk(self, seg_dirty) -> None:
        """Per-sparse-chunk device metric update: one jitted accumulator
        dispatch plus reference re-binds — zero device→host transfers."""
        if self._mstate is None:
            self._mstate = (jnp.zeros((), jnp.int32),
                            jnp.zeros((len(self._obs_caps),), jnp.int32),
                            jnp.zeros((len(self._obs_frac_edges) + 1,),
                                      jnp.int32))
        with self.metrics.tracer.span("runner.obs_accum"):
            self._mstate = self._obs_accum()(self._mstate, seg_dirty)
        self._m_dispatches.add(1)
        total, picks, frac = self._mstate
        self._m_dirty.set_device(total)
        self._m_picks.set_device(picks)
        self._m_frac.set_device(frac)
        # dirty_stats() reads the same accumulator (runner-local view)
        self._dirty_units = total

    # -- geometry ------------------------------------------------------------
    @property
    def _K(self) -> int:
        return self.n_keys

    @property
    def _U(self) -> int:
        return self.n_keys * self.n_segs

    def _names(self):
        return sorted(self.spec.input_specs)

    def ingest_sharding(self) -> Optional[NamedSharding]:
        """The sharding :meth:`step` ingests a chunk's leaves with under
        mesh placement: split by key over the mesh axis when keyed (each
        device holds its own keys' rows, as it holds their carried state),
        a full copy on every device when single-keyed (every shard's
        windows read the one buffer).  ``None`` under local placement,
        where a chunk is taken where it lies.  A caller that commits
        chunks to it ahead of :meth:`step` (the serve loop's double
        buffer) spares the step a transfer of its own."""
        mesh = self.policy.mesh
        if mesh is None:
            return None
        return NamedSharding(
            mesh, P(self.policy.axis) if self.policy.keyed else P())

    def _place(self, tree):
        """Device placement of carried per-key state (key-axis sharding)."""
        sh = self.ingest_sharding()
        if sh is None or not self.policy.keyed:
            return tree
        return _tm(lambda x: jax.device_put(x, sh), tree)

    def _commit(self, x, sh):
        """One chunk leaf on the ingest sharding ``sh``: an array already
        there is taken as it is; anything else is put here, one explicit
        transfer, counted in ``runner.ingest_puts``."""
        if isinstance(x, jax.Array) and x.sharding.is_equivalent_to(
                sh, x.ndim):
            return x
        self._m_ingest_puts.add(1)
        return jax.device_put(x, sh)

    def _unit_flags(self, flags):
        """Pin the per-unit flags ``(K, n_segs)`` (the sparse step's
        ``seg_dirty`` output, the metrics accumulator's and revision
        step's input) to one sharding under mesh placement — keys split
        when keyed, replicated when single — so the steps compiled ahead
        of time agree on it.  Local placement: unchanged."""
        mesh = self.policy.mesh
        if mesh is None:
            return flags
        return jax.lax.with_sharding_constraint(flags, NamedSharding(
            mesh, P(self.policy.axis) if self.policy.keyed else P()))

    # every configuration degree of freedom the staged steps close over;
    # _cache_key is built from exactly these (in this order) so the staging
    # cache can never be keyed on less than the traces depend on.  The
    # recompile-hazard pass (repro.analysis) probes this contract: perturb
    # one DOF on a sibling runner, check the key really moves.
    _KEY_DOFS = ("K", "n_segs", "mesh", "axis", "jit")

    def staging_key_dofs(self) -> Dict:
        """The staging-cache key's degrees of freedom, by name."""
        return {"K": self._K, "n_segs": self.n_segs,
                "mesh": self.policy.mesh, "axis": self.policy.axis,
                "jit": self.spec.jit}

    def _cache_key(self, kind, *extra):
        dofs = self.staging_key_dofs()
        return (kind,) + tuple(dofs[k] for k in self._KEY_DOFS) + extra

    def _stage(self, key, fn, name: str, donate=()):
        """Jit + cache one staged step under a stable program name (the
        compiled module reads ``jit_<name>`` in a device trace); the raw
        traced fn and its donation contract stay inspectable at
        ``("raw",) + key`` for the static auditor (repro.analysis), which
        re-traces them under ``jax.make_jaxpr`` instead of guessing from
        the compiled form."""
        fn.__name__ = name
        cache = self.spec.step_cache
        cache[("raw",) + key] = (fn, tuple(donate))
        cache[key] = (jax.jit(fn, donate_argnums=tuple(donate))
                      if self.spec.jit else fn)
        return cache[key]

    def _compile_label(self, key) -> str:
        """Human-readable compile-counter key for a step_cache key (the
        recompile detector's unit of accounting)."""
        kind, K, n_segs, mesh, axis = key[0], key[1], key[2], key[3], key[4]
        parts = [f"K={K}", f"segs={n_segs}"]
        if mesh is not None:
            parts.append(f"mesh={axis}")
        parts += [str(x) for x in key[6:]]
        return f"{kind}({','.join(parts)})"

    def _shard_body(self, fn, n_buf_args: int, unit_bufs: bool = False):
        """Wrap the per-unit compute ``fn(w, bufs...)`` in shard_map over
        the work-unit axis when a mesh is placed.  ``unit_bufs`` marks the
        buffer args as already per-unit (dense path: gathered windows shard
        with the units); otherwise they are the raw chunk buffers, which
        shard with the keys when keyed and replicate when single-keyed
        (each shard gathers its own segments from the full buffer)."""
        mesh, axis = self.policy.mesh, self.policy.axis
        if mesh is None:
            return fn
        from jax.experimental.shard_map import shard_map
        buf_spec = P(axis) if (unit_bufs or self.policy.keyed) else P()
        return shard_map(
            fn, mesh=mesh,
            in_specs=(P(axis),) + (buf_spec,) * n_buf_args,
            out_specs=P(axis), check_rep=False)

    def _per_key(self, fn):
        """Run ``fn(values, valid)`` — per-key work over whole key rows —
        on each device's own keys under mesh placement.  A Pallas kernel
        cannot be partitioned automatically, so the change-detection
        kernel must sit inside shard_map: keys split when keyed, the
        replicated buffer of a single-key stream read whole everywhere."""
        mesh = self.policy.mesh
        if mesh is None:
            return fn
        from jax.experimental.shard_map import shard_map
        spec = P(self.policy.axis) if self.policy.keyed else P()
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_rep=False)

    # -- chunk ingest --------------------------------------------------------
    def _ingest(self, chunks: Dict[str, SnapshotGrid]) -> Dict[str, tuple]:
        chunk_in = {}
        sh = self.ingest_sharding()
        for name in self._names():
            s = self.spec.input_specs[name]
            g = chunks[name]
            want = ((self.n_keys, s.core * self.n_segs) if self.policy.keyed
                    else (s.core * self.n_segs,))
            if tuple(g.valid.shape) != want:
                raise ValueError(
                    f"input {name}: chunk validity shape "
                    f"{tuple(g.valid.shape)} != expected {want}")
            v, m = g.value, g.valid
            if sh is not None:
                v, m = _tm(lambda x: self._commit(x, sh), (v, m))
            if not self.policy.keyed:  # internal layout always carries K
                v, m = _tm(lambda x: x[None], v), m[None]
            chunk_in[name] = (v, m)
        return chunk_in

    def _init_missing_tails(self, chunk_in: Dict[str, tuple]) -> None:
        K = self._K
        for name in self._names():
            if name in self._tails:
                continue
            hl = self.spec.input_specs[name].left_halo
            cv, cm = chunk_in[name]
            tv = _tm(lambda x: jnp.zeros((K, hl) + x.shape[2:], x.dtype), cv)
            self._tails[name] = self._place((tv, jnp.zeros((K, hl), bool)))
            if self._sparse is not None and name not in self._sparse["dirty"]:
                self._sparse["dirty"][name] = jnp.zeros((K, hl), bool)
                if hl == 0:
                    # the 1-tick snapshot is only ever read for halo-free
                    # inputs (tick 0's diff partner); halo-carrying inputs
                    # get their position-0 flag from the dirty tail, so
                    # carrying a snapshot for them would be dead state
                    self._sparse["prev"][name] = (
                        _tm(lambda x: jnp.zeros((K, 1) + x.shape[2:],
                                                x.dtype), cv),
                        jnp.zeros((K, 1), bool))

    # -- dense step ----------------------------------------------------------
    def _dense_step(self):
        key = self._cache_key("dense")
        cache = self.spec.step_cache
        if key in cache:
            return cache[key]
        self.metrics.tracer.record_compile(self._compile_label(key))
        names, specs = self._names(), self.spec.input_specs
        outs_fn = self.spec.outs_fn
        K, n_segs, U = self._K, self.n_segs, self._U
        # static per-input gather map: segment k's halo window starts at
        # buffer tick k·core (the carried tail supplies segment 0's halo)
        idx_maps = {
            name: np.arange(n_segs)[:, None] * specs[name].core
            + np.arange(specs[name].length)[None, :] for name in names}

        def units_body(*flat):
            def one(*f):
                return outs_fn(dict(zip(names, f)))
            return jax.vmap(one)(*flat)

        def units_sharded(w, *flat):  # w unused: dense computes every unit
            return units_body(*flat)

        sharded = self._shard_body(units_sharded, len(names), unit_bufs=True)

        def step(tails, chunks):
            full, units = {}, []
            for name in names:
                tv, tm = tails[name]
                cv, cm = chunks[name]
                fv = _tm(lambda a, b: jnp.concatenate([a, b], axis=1), tv, cv)
                fm = jnp.concatenate([tm, cm], axis=1)
                full[name] = (fv, fm)
                L = specs[name].length
                idx = jnp.asarray(idx_maps[name])
                gv = _tm(lambda x: jnp.take(x, idx, axis=1).reshape(
                    (U, L) + x.shape[2:]), fv)
                gm = jnp.take(fm, idx, axis=1).reshape(U, L)
                units.append((gv, gm))
            with jax.named_scope("tilt.compute"):
                outs = sharded(jnp.ones((U,), bool), *units)
            outs = {o: (_tm(lambda x: x.reshape(
                        (K, n_segs * x.shape[1]) + x.shape[2:]), ov),
                        om.reshape(K, -1))
                    for o, (ov, om) in outs.items()}
            new_tails = {}
            for name in names:
                s = specs[name]
                lo = s.core * n_segs
                fv, fm = full[name]
                new_tails[name] = (
                    _tm(lambda x: jax.lax.slice_in_dim(
                        x, lo, lo + s.left_halo, axis=1), fv),
                    jax.lax.slice_in_dim(fm, lo, lo + s.left_halo, axis=1))
            return outs, new_tails

        # the carried tails are runner-owned (step outputs, or zeros /
        # restore-copies) — donate them so steady-state chunks update the
        # halo buffers in place instead of reallocating
        return self._stage(key, step, "tilt_dense_step", donate=(0,))

    # -- sparse body (one fused jitted step per chunk) -----------------------
    #
    # The three phases that used to run as separate jitted calls — mask
    # (diff + ChangePlan dilation + per-unit reduction), compute (per-shard
    # compaction gather → vmapped body → scatter) and hold — are traced into
    # ONE step: the capacity bucket is picked on device (`searchsorted` over
    # the ladder + `lax.switch`), so a steady-state chunk issues zero
    # device→host transfers, and the carried state pytree is donated so
    # tails/snapshots/seeds update in place.

    def _compute_local(self, cap: int):
        """Per-shard compute body for one compaction capacity: resolve the
        local dirty units (local ``nonzero`` into the power-of-two bucket),
        gather their halo windows, run the vmapped body on them only,
        scatter the results back over the local unit axis.  Cached per
        capacity — these are the branches of the fused step's
        ``lax.switch`` ladder (and the observable record of which buckets
        this geometry can run)."""
        key = self._cache_key("compute", cap)
        cache = self.spec.step_cache
        if key in cache:
            return cache[key]
        self.metrics.tracer.record_compile(self._compile_label(key))
        names, specs = self._names(), self.spec.input_specs
        outs_fn = self.spec.outs_fn
        n_segs = self.n_segs
        keyed = self.policy.keyed
        mesh, axis = self.policy.mesh, self.policy.axis
        U_loc = self._U // self.policy.n_shards

        full_cap = cap == U_loc
        # segments per buffer row: a keyed shard holds whole keys, a
        # single-keyed shard U_loc segments of the one (replicated) row
        per_row = n_segs if keyed else U_loc

        def one(*f):
            return outs_fn(dict(zip(names, f)))

        # the phases are named scopes (``tilt.*`` in each op's metadata, so
        # a device trace attributes op time to them); they sit in here so
        # every branch of the capacity ladder carries them
        def local(w, *flat):
            with jax.named_scope("tilt.compact"):
                # a single-keyed shard's segments start at its offset in
                # the replicated buffer
                seg0 = (jax.lax.axis_index(axis) * U_loc
                        if mesh is not None and not keyed else 0)
                if full_cap:
                    # full-capacity bucket (count > U_loc/2): compaction
                    # saves nothing, so compute every unit in place —
                    # static window slices, no nonzero, identity scatter.
                    # Bit-identical: computing a clean unit yields exactly
                    # its hold value (the sparse exactness contract), and
                    # the hold fill downstream still overwrites clean units
                    # from the dirty chain.
                    ids, pos = None, None
                else:
                    nz = jnp.nonzero(w, size=cap, fill_value=0)[0]
                    ids = (nz // per_row, nz % per_row)
                    pos = jnp.clip(jnp.cumsum(w) - 1, 0, cap - 1)
            with jax.named_scope("tilt.gather"):
                gath = []
                for name, (bv, bm) in zip(names, flat):
                    s = specs[name]

                    def win(x, s=s):
                        return _unit_windows(x, s.core, s.length, seg0,
                                             ids=ids, per_row=per_row)

                    gath.append((_tm(win, bv), win(bm)))
            with jax.named_scope("tilt.compute"):
                outs = jax.vmap(one)(*gath)              # {o: (cap, S_o, …)}
            if full_cap:
                return outs
            with jax.named_scope("tilt.scatter"):
                return {o: (_tm(lambda x: jnp.take(x, pos, axis=0), ov),
                            jnp.take(om, pos, axis=0))
                        for o, (ov, om) in outs.items()}  # (U_loc, S_o, …)

        cache[key] = local
        return cache[key]

    def _hold_local(self):
        """Hold fill (global): clean units take the last tick of the
        nearest preceding dirty segment of the same key, or the key's
        carried hold seed; dirty units keep their computed results."""
        K, n_segs = self._K, self.n_segs

        def hold(full_outs, seg_dirty, seeds):
            ar = jnp.arange(n_segs)
            prev_d = jax.lax.cummax(
                jnp.where(seg_dirty, ar[None, :], -1), axis=1)
            src = jnp.clip(prev_d, 0, n_segs - 1)        # (K, n_segs)
            has = prev_d >= 0
            take_seg = jax.vmap(lambda x, s: jnp.take(x, s, axis=0))
            outs, new_seeds = {}, {}
            for o, (fv, fm) in full_outs.items():        # fv (K, n_segs, S, …)
                sv, sm = seeds[o]

                def hold_leaf(x, seed):
                    hx = take_seg(x[:, :, -1], src)      # (K, n_segs, …)
                    hx = jnp.where(_bc(has, hx), hx,
                                   jnp.expand_dims(seed, 1).astype(x.dtype))
                    return jnp.where(_bc(seg_dirty, x), x,
                                     jnp.expand_dims(hx, 2))

                ov = _tm(hold_leaf, fv, sv)
                hm = jnp.where(has, take_seg(fm[:, :, -1], src), sm[:, None])
                om = jnp.where(seg_dirty[:, :, None], fm, hm[:, :, None])
                ov = _tm(lambda x: x.reshape(
                    (K, n_segs * x.shape[2]) + x.shape[3:]), ov)
                om = om.reshape(K, -1)
                outs[o] = (ov, om)
                new_seeds[o] = (_tm(lambda x: x[:, -1], ov), om[:, -1])
            return outs, new_seeds

        return hold

    def _fused_sparse_step(self, force_first: bool):
        """The whole sparse chunk as one traced step: mask → device-side
        bucket pick → per-shard compacted compute → hold.

        ``step(tails, dirty, prev, seeds, chunks)`` returns ``(outs,
        new_tails, new_dirty, new_prev, new_seeds, seg_dirty)``.  Two
        variants per geometry: ``force_first=True`` (stream start / missing
        hold seed: segment 0 of every key is forced dirty, nothing is
        donated because the zero seeds are cached) and the steady-state
        variant, which donates the carried state pytree — every donated
        argument is an output of the previous step (or a restore-time
        copy), so the tails, dirty tails, snapshots and hold seeds update
        in place.
        """
        key = self._cache_key("sparse_fused", force_first)
        cache = self.spec.step_cache
        if key in cache:
            return cache[key]
        self.metrics.tracer.record_compile(self._compile_label(key))
        names, specs = self._names(), self.spec.input_specs
        cp = self.spec.change_plan
        S, q = self.spec.out_len, self.spec.out_prec
        K, n_segs, U = self._K, self.n_segs, self._U

        # static per-input lineage geometry (the ChangePlan lowered to the
        # affine form the fused kernel consumes) + the segments a carried
        # position-0 change flag dirties (tick 0 is outside the kernel's
        # convention: its diff partner lives before the buffer)
        geom, hits0 = {}, {}
        ks = np.arange(n_segs)
        for name in names:
            s, sp = specs[name], cp.specs[name]
            a0, stp, width = seg_range_affine(
                sp.lookback, sp.lookahead, s.prec,
                grid_t0=-s.left_halo * s.prec, out_t0=0, out_prec=q,
                seg_len=S)
            geom[name] = (a0, stp, width)
            lo = a0 + ks * stp
            hits0[name] = (lo <= 0) & (lo + width > 0)

        ladder = sparse_mod.capacity_ladder(U // self.policy.n_shards)
        branches = [self._compute_local(c) for c in ladder]
        caps = np.asarray(ladder, np.int32)
        hold = self._hold_local()

        def switched(w, *flat):
            with jax.named_scope("tilt.compact"):
                cnt = jnp.sum(w.astype(jnp.int32))
                b = jnp.searchsorted(jnp.asarray(caps), cnt, side="left")
            return jax.lax.switch(b, branches, w, *flat)

        sharded = self._shard_body(switched, len(names))

        def tick0_diff(cv, cm, pv, pm):
            d = cm[:, 0] != pm[:, 0]
            for x, p in zip(jax.tree_util.tree_leaves(cv),
                            jax.tree_util.tree_leaves(pv)):
                neq = x[:, 0] != p[:, 0].astype(x.dtype)
                if neq.ndim > 1:
                    neq = neq.reshape(neq.shape[0], -1).any(axis=1)
                d = d | neq
            return d

        def adj_diff(sv, sm):
            nd = sm[:, 1:] != sm[:, :-1]
            for x in jax.tree_util.tree_leaves(sv):
                neq = x[:, 1:] != x[:, :-1]
                if neq.ndim > 2:
                    neq = neq.reshape(neq.shape[:2] + (-1,)).any(axis=2)
                nd = nd | neq
            return nd

        def detect(tails, dirty, prev, chunks):
            """Change detection: each input's buffer (carried tail +
            chunk), its per-segment dirty flags, and the carried tails,
            dirty tails and snapshots of the next chunk."""
            bufs, new_tails, new_dirty, new_prev = {}, {}, {}, {}
            seg_dirty = jnp.zeros((K, n_segs), bool)
            for name in names:
                s = specs[name]
                hl = s.left_halo
                tv, tm = tails[name]
                cv, cm = chunks[name]
                fv = _tm(lambda a, b: jnp.concatenate([a, b], axis=1), tv, cv)
                fm = jnp.concatenate([tm, cm], axis=1)
                bufs[name] = (fv, fm)
                g = geom[name]

                def one_key(v, m, g=g):
                    mats = sparse_compact.grid_mats(v, m)
                    return sparse_compact.seg_dirty(
                        mats, [g] * len(mats), n_segs)

                sd = self._per_key(jax.vmap(one_key))(fv, fm)  # (K, n_segs)
                # buffer position 0: carried change flag (its diff partner
                # is one tick before the buffer); with no tail the carried
                # 1-tick snapshot supplies the partner
                d0 = (dirty[name][:, 0] if hl
                      else tick0_diff(cv, cm, *prev[name]))
                seg_dirty = (seg_dirty | sd
                             | (d0[:, None] & jnp.asarray(hits0[name])))
                lo = s.core * n_segs
                new_tails[name] = (
                    _tm(lambda x: jax.lax.slice_in_dim(
                        x, lo, lo + hl, axis=1), fv),
                    jax.lax.slice_in_dim(fm, lo, lo + hl, axis=1))
                if hl:
                    # carried dirty tail = adjacent diffs of the buffer's
                    # last hl+1 ticks (identical to the flags a full-length
                    # mask would carry: every tail position has its diff
                    # partner in the buffer, since lo >= 1)
                    new_dirty[name] = adj_diff(
                        _tm(lambda x: jax.lax.slice_in_dim(
                            x, lo - 1, lo + hl, axis=1), fv),
                        jax.lax.slice_in_dim(fm, lo - 1, lo + hl, axis=1))
                else:
                    new_dirty[name] = dirty[name]
                if not hl:
                    # snapshot carried (and donated in-place) only where it
                    # will be read: halo-free inputs' next tick-0 diff
                    new_prev[name] = (_tm(lambda x: x[:, -1:], cv),
                                      cm[:, -1:])
            if not names:
                seg_dirty = jnp.ones((K, n_segs), bool)  # input-free: dense
            if force_first:
                seg_dirty = seg_dirty.at[:, 0].set(True)
            seg_dirty = self._unit_flags(seg_dirty)
            return bufs, new_tails, new_dirty, new_prev, seg_dirty

        def step(tails, dirty, prev, seeds, chunks):
            with jax.named_scope("tilt.change_detect"):
                bufs, new_tails, new_dirty, new_prev, seg_dirty = detect(
                    tails, dirty, prev, chunks)
            full = sharded(seg_dirty.reshape(U),
                           *[bufs[nm] for nm in names])
            full = {o: (_tm(lambda x: x.reshape(
                            (K, n_segs) + x.shape[1:]), fv),
                        fm.reshape((K, n_segs) + fm.shape[1:]))
                    for o, (fv, fm) in full.items()}
            with jax.named_scope("tilt.hold"):
                outs, new_seeds = hold(full, seg_dirty, seeds)
            return outs, new_tails, new_dirty, new_prev, new_seeds, seg_dirty

        return self._stage(key, step,
                           "tilt_sparse_first" if force_first
                           else "tilt_sparse_steady",
                           donate=() if force_first else (0, 1, 2, 3))

    def _zero_seeds(self, chunk_in):
        """φ hold seeds shaped like one output tick per key (unread: any
        output missing a carried seed forces its first segment dirty)."""
        if getattr(self, "_zero_seed_cache", None) is not None:
            return self._zero_seed_cache
        avals = {}
        for name in self._names():
            s = self.spec.input_specs[name]
            cv, cm = chunk_in[name]
            avals[name] = (
                _tm(lambda x: jax.ShapeDtypeStruct(
                    (s.length,) + x.shape[2:], x.dtype), cv),
                jax.ShapeDtypeStruct((s.length,), jnp.bool_))
        shapes = jax.eval_shape(self.spec.outs_fn, avals)
        K = self._K
        self._zero_seed_cache = {
            o: (_tm(lambda a: jnp.zeros((K,) + a.shape[1:], a.dtype), ov),
                jnp.zeros((K,), bool))
            for o, (ov, om) in shapes.items()}
        return self._zero_seed_cache

    def _sparse_chunk(self, chunk_in):
        st = self._sparse
        missing_seed = any(o not in st["seed"] for o in self.spec.out_precs)
        force_first = (not st["started"]) or missing_seed
        if force_first:
            seeds = dict(self._zero_seeds(chunk_in))
            seeds.update(st["seed"])
        else:
            seeds = st["seed"]
        step = self._fused_sparse_step(force_first)
        with self.metrics.tracer.span("runner.dispatch"):
            outs, new_tails, new_dirty, new_prev, new_seeds, seg_dirty = \
                step(self._tails, st["dirty"], st["prev"], seeds, chunk_in)
        # device-resident diagnostics: no transfer, no dispatch stall
        self.last_seg_dirty = seg_dirty
        if self.metrics.on:
            self._m_dispatches.add(1)
            self._obs_sparse_chunk(seg_dirty)
            if not force_first:
                self._m_donated.add(1)
        else:
            cnt = seg_dirty.sum(dtype=jnp.int32)
            self._dirty_units = (cnt if self._dirty_units is None
                                 else self._dirty_units + cnt)
        self._total_units += self._U
        self._chunks_run += 1

        def commit():
            self._tails = new_tails
            st["dirty"], st["prev"] = new_dirty, new_prev
            st["seed"], st["started"] = new_seeds, True

        return outs, commit

    def _postprocess(self, outs):
        """The eager per-chunk result assembly between the staged step and
        the returned grids: drop the internal K axis for single-key
        runners.  reshape, not x[0]: eager indexing binds a dynamic_slice
        whose start-index scalars are host→device transfers on every
        chunk — reshape is metadata-only.  This is the only eager array
        code on the chunk path, and the transfer-freedom pass
        (repro.analysis) lints exactly that: any non-metadata eqn outside
        the staged step in the whole-chunk jaxpr is a finding."""
        if self.policy.keyed:
            return outs
        return {o: (_tm(lambda x: x.reshape(x.shape[1:]), v),
                    m.reshape(m.shape[1:]))
                for o, (v, m) in outs.items()}

    # -- static audit surface (repro.analysis) -------------------------------
    def audit_example_chunks(self) -> Dict[str, SnapshotGrid]:
        """Zero-filled example chunks in the external :meth:`step` layout,
        sized to this runner's geometry — concrete arguments for tracing
        the chunk path without data."""
        chunks = {}
        for name in self._names():
            s = self.spec.input_specs[name]
            shape = ((self.n_keys, s.core * self.n_segs) if self.policy.keyed
                     else (s.core * self.n_segs,))
            chunks[name] = SnapshotGrid(
                value=jnp.zeros(shape, jnp.float32),
                valid=jnp.zeros(shape, bool), t0=0, prec=s.prec)
        return chunks

    def _audit_state(self, chunk_in):
        """Fresh-stream carried state (tails / dirty / prev / seeds) for
        audit tracing, built without touching the live stream state."""
        saved = self._tails, self._sparse
        self._tails = {}
        if self.policy.sparse:
            self._sparse = {"dirty": {}, "prev": {}, "seed": {},
                            "started": False}
        try:
            self._init_missing_tails(chunk_in)
            tails, sparse = self._tails, self._sparse
        finally:
            self._tails, self._sparse = saved
        seeds = self._zero_seeds(chunk_in) if self.policy.sparse else None
        return tails, sparse, seeds

    def staged_steps(self, chunks: Optional[Dict] = None):
        """The staged (jitted) steps one chunk dispatches, with concrete
        example arguments — the lowerable audit surface
        ``repro.analysis`` traces under ``jax.make_jaxpr``.

        Returns a list of dicts ``{label, key, fn, raw, donate, args}``:
        ``fn`` is the cached jitted step, ``raw`` the untraced function it
        was staged from, ``donate`` its ``donate_argnums`` contract and
        ``args`` a concrete argument tuple matching the real chunk-path
        call.  Building these populates the shared step cache exactly like
        a real first chunk would (cache hits thereafter — no extra
        compiles are recorded)."""
        chunks = chunks if chunks is not None else self.audit_example_chunks()
        chunk_in = self._ingest(chunks)
        tails, sparse, seeds = self._audit_state(chunk_in)
        cache = self.spec.step_cache

        def entry(label, key, fn, args):
            raw, donate = cache.get(("raw",) + key, (None, ()))
            return {"label": label, "key": key, "fn": fn, "raw": raw,
                    "donate": donate, "args": args}

        steps = []
        if self.policy.sparse:
            for force_first in (True, False):
                fn = self._fused_sparse_step(force_first)
                key = self._cache_key("sparse_fused", force_first)
                label = ("sparse_fused(first)" if force_first
                         else "sparse_fused(steady)")
                steps.append(entry(label, key, fn,
                                   (tails, sparse["dirty"], sparse["prev"],
                                    seeds, chunk_in)))
            if self.metrics.on:
                fn = self._obs_accum()
                key = self._cache_key("obs_accum")
                mstate = (jnp.zeros((), jnp.int32),
                          jnp.zeros((len(self._obs_caps),), jnp.int32),
                          jnp.zeros((len(self._obs_frac_edges) + 1,),
                                    jnp.int32))
                steps.append(entry(
                    "obs_accum", key, fn,
                    (mstate, self._unit_flags(
                        jnp.zeros((self._K, self.n_segs), bool)))))
        else:
            fn = self._dense_step()
            key = self._cache_key("dense")
            steps.append(entry("dense", key, fn, (tails, chunk_in)))
        if self._rev_ring is not None:
            fn = self._revision_step()
            key = self._cache_key("revise")
            steps.append(entry("revise", key, fn,
                               (tails, chunk_in, self._unit_flags(
                                   jnp.zeros((self._K, self.n_segs),
                                             bool)))))
        return steps

    def chunk_fn(self, variant: str = "steady", chunks: Optional[Dict] = None):
        """A pure whole-chunk function plus concrete example args: the
        staged step dispatch *and* the eager post-step result assembly,
        exactly as :meth:`step` composes them.  Tracing this under
        ``jax.make_jaxpr`` shows every op a chunk binds outside the staged
        step — the transfer-freedom pass's audit surface.

        ``variant``: ``"steady"`` / ``"first"`` (sparse bodies) or
        ``"dense"``.
        """
        chunks = chunks if chunks is not None else self.audit_example_chunks()
        chunk_in = self._ingest(chunks)
        tails, sparse, seeds = self._audit_state(chunk_in)
        if self.policy.sparse:
            if variant not in ("steady", "first"):
                raise ValueError(
                    f"sparse body has chunk variants 'steady'/'first', "
                    f"not {variant!r}")
            staged = self._fused_sparse_step(variant == "first")

            def fn(tails, dirty, prev, seeds, chunk_in):
                outs, *new_state = staged(tails, dirty, prev, seeds, chunk_in)
                return self._postprocess(outs), tuple(new_state)

            args = (tails, sparse["dirty"], sparse["prev"], seeds, chunk_in)
        else:
            if variant not in ("steady", "dense"):
                raise ValueError(
                    f"dense body has chunk variant 'dense', not {variant!r}")
            staged = self._dense_step()

            def fn(tails, chunk_in):
                outs, new_tails = staged(tails, chunk_in)
                return self._postprocess(outs), new_tails

            args = (tails, chunk_in)
        return fn, args

    # -- AOT serving surface (repro.serve) -----------------------------------
    def aot_keys(self) -> List[tuple]:
        """``(label, staging-cache key)`` of every staged step one serving
        process dispatches at this policy point — the AOT compilation
        surface :func:`repro.serve.aot.aot_compile` covers.  Enumerable
        without staging anything, so a warm start can probe the persisted
        executable cache before any getter records a compile."""
        keys = []
        if self.policy.sparse:
            keys.append(("sparse_fused(first)",
                         self._cache_key("sparse_fused", True)))
            keys.append(("sparse_fused(steady)",
                         self._cache_key("sparse_fused", False)))
            if self.metrics.on:
                keys.append(("obs_accum", self._cache_key("obs_accum")))
        else:
            keys.append(("dense", self._cache_key("dense")))
        if self._rev_ring is not None:
            keys.append(("revise", self._cache_key("revise")))
        return keys

    def install_executable(self, key, fn, *, label: str = "",
                           how: str = "loaded", donate=()) -> None:
        """Executable-serialization hook: put an AOT executable (a
        ``jax.stages.Compiled`` / deserialized ``Loaded``) into the step
        cache under its staging key.  Installing *before* the step getters
        run makes them cache hits, so a warm start records zero compiles
        (the tracer-verified warm-start proof) and never traces the body.
        The donation contract is baked into the executable at lowering
        time; ``donate`` just records it for the serving analysis pass."""
        if not self.spec.jit:
            raise ValueError(
                "AOT executables need a jitted body (spec.jit=True)")
        self.spec.step_cache[key] = fn
        self.aot_record[key] = {"label": label or key[0], "how": how,
                                "donate": tuple(donate)}
        self.metrics.tracer.record_aot(self._compile_label(key), how)

    def seed_shape_spec(self):
        """``jax.ShapeDtypeStruct`` tree of the φ hold seeds (sparse
        bodies; ``None`` for dense) — pickles, so a persisted plan
        artifact lets a fresh process :meth:`prime_seed_shapes` and skip
        the one remaining trace on the warm path (``jax.eval_shape`` of
        ``outs_fn`` in :meth:`_zero_seeds`)."""
        if not self.policy.sparse:
            return None
        seeds = self._zero_seeds(self._ingest(self.audit_example_chunks()))
        return {o: (_tm(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        ov),
                    jax.ShapeDtypeStruct(om.shape, om.dtype))
                for o, (ov, om) in seeds.items()}

    def prime_seed_shapes(self, shapes) -> None:
        """Install persisted seed shapes (:meth:`seed_shape_spec` of a
        previous process) so the first sparse chunk skips the
        ``eval_shape`` trace of ``outs_fn`` — with AOT-installed steps
        this makes first-result completely trace-free."""
        if shapes is None or not self.policy.sparse:
            return
        self._zero_seed_cache = {
            o: (_tm(lambda a: jnp.zeros(a.shape, a.dtype), ov),
                jnp.zeros(om.shape, om.dtype))
            for o, (ov, om) in shapes.items()}

    # -- public API ----------------------------------------------------------
    def step(self, chunks: Dict[str, SnapshotGrid]):
        """Advance the stream by one chunk (``segs_per_chunk`` segments).

        Each chunk grid supplies ``segs_per_chunk · spec.core`` fresh ticks
        per input (leading key axis first when ``keys='vmapped'``).  Returns
        one output grid (solo) or ``{query_name: grid}`` (union).  Carried
        state commits only after the step succeeded, so a raise leaves the
        runner exactly as it was.
        """
        tracer = self.metrics.tracer
        with tracer.span("runner.step") as took:
            snap = None
            if self._rev_ring is not None:
                # pre-chunk state snapshot for the revision ring: captured
                # before dispatch (the donating step consumes the tails),
                # as a host pytree — one device sync per chunk, the
                # documented cost of revisability (docs/architecture.md
                # "Out-of-order ingestion"); hot paths that never see late
                # data leave the ring disabled and keep the zero-sync
                # steady state
                snap = {"chunk": self._t // (self.n_segs * self.spec.span),
                        "state": self.state()}
            with tracer.span("runner.ingest"):
                chunk_in = self._ingest(chunks)
                self._init_missing_tails(chunk_in)
            if self.policy.sparse:
                outs, commit = self._sparse_chunk(chunk_in)
            else:
                step = self._dense_step()
                with tracer.span("runner.dispatch"):
                    outs, new_tails = step(self._tails, chunk_in)
                if self.metrics.on:
                    self._m_dispatches.add(1)
                    if self.spec.jit:
                        self._m_donated.add(1)

                def commit(new_tails=new_tails):
                    self._tails = new_tails

            with tracer.span("runner.commit"):
                result = {}
                for o, (v, m) in self._postprocess(outs).items():
                    result[o] = SnapshotGrid(value=v, valid=m, t0=self._t,
                                             prec=self.spec.out_precs[o])
                commit()
                if snap is not None:
                    self._rev_ring.append(snap)
                self._t += self.n_segs * self.spec.span
        if self.metrics.on:
            # host-side arithmetic only: the span's wall time around the
            # async dispatch, never a device read
            self._m_chunks.add(1)
            self._m_units.add(self._U)
            self._m_lat.observe(took.seconds)
        return result["__out"] if self.spec.solo else result

    def run(self, inputs: Dict[str, SnapshotGrid], n_chunks: int):
        """Slice ``n_chunks`` chunks from full streams, step through them
        and stitch the outputs along time."""
        taxis = 1 if self.policy.keyed else 0
        outs = []
        for c in range(n_chunks):
            chunk = {}
            for name in self._names():
                s = self.spec.input_specs[name]
                g = inputs[name]
                lo = c * s.core * self.n_segs
                chunk[name] = SnapshotGrid(
                    value=_tm(lambda x: jax.lax.slice_in_dim(
                        x, lo, lo + s.core * self.n_segs, axis=taxis),
                        g.value),
                    valid=jax.lax.slice_in_dim(
                        g.valid, lo, lo + s.core * self.n_segs, axis=taxis),
                    t0=g.t0 + lo * s.prec, prec=s.prec)
            outs.append(self.step(chunk))

        def stitch(parts):
            value = _tm(lambda *xs: jnp.concatenate(xs, axis=taxis),
                        *[p.value for p in parts])
            valid = jnp.concatenate([p.valid for p in parts], axis=taxis)
            return SnapshotGrid(value=value, valid=valid, t0=parts[0].t0,
                                prec=parts[0].prec)

        if self.spec.solo:
            return stitch(outs)
        return {o: stitch([c[o] for c in outs]) for o in outs[0]}

    def reset(self) -> None:
        """Drop carried state; the next step starts a fresh stream at t=0."""
        self._tails = {}
        if self._sparse is not None:
            self._sparse = {"dirty": {}, "prev": {}, "seed": {},
                            "started": False}
        self._t = 0
        self.last_seg_dirty = None
        self._dirty_units = None
        self._total_units = 0
        self._chunks_run = 0
        if self._rev_ring is not None:
            self._rev_ring.clear()
        if self._mstate is not None:
            # preserve the registry's running totals (syncs — off-path),
            # then drop this runner's device accumulator state
            self._m_dirty.fold_device()
            if self._m_picks is not None:
                self._m_picks.fold_device()
            self._m_frac.fold_device()
            self._mstate = None

    def dirty_stats(self) -> Optional[Dict]:
        """Measured compaction of the sparse body since construction/reset:
        ``{chunks, units, dirty_units, compact}`` where ``compact`` is the
        fraction of (key × segment) work units that actually computed
        (forced-dirty first segments included).  ``None`` for dense bodies
        or before the first chunk.

        Compat wrapper over the runner-local view of the metrics
        registry's device accumulator (``runner.dirty_units`` et al. —
        prefer ``runner.metrics.snapshot()``, which carries the same
        numbers plus bucket picks, dirty-fraction and latency
        histograms).  Reading syncs the device-resident counter — a
        diagnostic call, not part of the steady-state path
        (``last_seg_dirty`` holds the raw per-unit flags of the newest
        chunk, also device-resident)."""
        if self._sparse is None or self._total_units == 0:
            return None
        dirty = int(self._dirty_units)
        return {"chunks": self._chunks_run, "units": self._total_units,
                "dirty_units": dirty,
                "compact": dirty / self._total_units}

    # -- checkpointing (the one state/validate path) -------------------------
    def _strip(self, tree):
        """Drop the internal K axis for single-key runners (host layout)."""
        if self.policy.keyed:
            return tree
        return _tm(lambda x: x[0], tree)

    def _lift(self, tree):
        if self.policy.keyed:
            return tree
        return _tm(lambda x: jnp.asarray(x)[None], tree)

    def state(self) -> Dict:
        """Checkpointable runner state (host arrays); see the module
        docstring for the pytree layout."""
        to_np = lambda t: _tm(np.asarray, t)  # noqa: E731
        out = {k: to_np(self._strip(v)) for k, v in self._tails.items()}
        out["__t"] = self._t
        if self._sparse is not None:
            st = self._sparse
            out["__sparse"] = {
                "dirty": {k: np.asarray(self._strip(v))
                          for k, v in st["dirty"].items()},
                "prev": {k: to_np(self._strip(v))
                         for k, v in st["prev"].items()},
                "seed": {o: to_np(self._strip(v))
                         for o, v in st["seed"].items()},
                "started": st["started"]}
        return out

    def restore(self, state: Dict, *, strict: bool = True) -> None:
        """Restore a :meth:`state` checkpoint, validating it against this
        runner's configuration first.

        Every inconsistency — wrong input names, wrong key count, wrong
        tail length (a checkpoint from a different query/plan), a stream
        clock misaligned with the partition span, missing or unexpected
        sparse change state — raises a ``ValueError`` naming the mismatch,
        instead of surfacing later as an opaque shape error inside the
        jitted step.  ``strict=False`` additionally tolerates inputs absent
        from the checkpoint (their tails re-initialize to φ) — the
        session's attach/detach re-fit path.
        """
        state = dict(state)
        if "__t" not in state:
            raise ValueError("checkpoint has no '__t' stream clock")
        t = state.pop("__t")
        span = self.spec.span
        if not isinstance(t, (int, np.integer)) or t < 0 or t % span:
            raise ValueError(
                f"checkpoint stream clock __t={t!r} is not a non-negative "
                f"multiple of the partition span {span} — was this saved "
                "from an engine with a different out_len/out_prec?")
        sparse_state = state.pop("__sparse", None)
        if self.policy.sparse and sparse_state is None:
            raise ValueError(
                "sparse engine cannot restore a dense checkpoint: no "
                "'__sparse' change state (dirty tails / snapshots / seed)")
        if not self.policy.sparse and sparse_state is not None:
            raise ValueError(
                "dense engine cannot restore a sparse checkpoint "
                "(carries '__sparse' change state)")
        specs = self.spec.input_specs
        names = set(specs)
        unknown = sorted(set(state) - names)
        missing = sorted(n for n in names - set(state)
                         if specs[n].left_halo > 0) if strict else []
        if state and (unknown or missing):
            raise ValueError(
                f"checkpoint inputs {sorted(state)} != query inputs "
                f"{sorted(names)} (unknown={unknown}, missing={missing})")
        K = self._K
        lead = ((K,) if self.policy.keyed else ())

        def check_lead(name, got, what):
            want = lead + (specs[name].left_halo,)
            label = ("(n_keys, left_halo)" if self.policy.keyed
                     else "(left_halo,)")
            if tuple(got) != want:
                raise ValueError(
                    f"input {name}: checkpoint {what} shape {tuple(got)} != "
                    f"{label} = {want}")

        for name, (tv, tm) in state.items():
            check_lead(name, np.shape(tm), "tail")
            for leaf in jax.tree_util.tree_leaves(tv):
                want = lead + (specs[name].left_halo,)
                if tuple(np.shape(leaf)[:len(lead) + 1]) != want:
                    label = ("(n_keys, left_halo)" if self.policy.keyed
                             else "(left_halo,)")
                    raise ValueError(
                        f"input {name}: checkpoint tail value leaf shape "
                        f"{tuple(np.shape(leaf))} does not lead with "
                        f"{label} = {want}")
        if sparse_state is not None:
            for name in state:
                got = np.shape(sparse_state["dirty"].get(name, ()))
                check_lead(name, got, "dirty-tail")
            if strict:
                # halo-free inputs carry their whole change lineage in the
                # 1-tick snapshot; restoring one without it would silently
                # treat an unchanged tick 0 as clean against φ
                no_prev = sorted(
                    n for n in state if specs[n].left_halo == 0
                    and n not in (sparse_state.get("prev") or {}))
                if no_prev:
                    raise ValueError(
                        f"checkpoint is missing the 1-tick 'prev' snapshot "
                        f"for halo-free inputs {no_prev}")

        self._t = int(t)
        # jnp.array (copy), not asarray: restored state feeds the donating
        # steady-state step, which must never consume the caller's buffers.
        self._tails = {k: self._place(self._lift(_tm(jnp.array, v)))
                       for k, v in state.items()}
        if self._sparse is not None:
            st = {"dirty": {}, "prev": {}, "seed": {}, "started": True}
            if sparse_state is not None:
                st["dirty"] = {
                    k: self._place(self._lift(jnp.array(v)))
                    for k, v in sparse_state["dirty"].items()
                    if k in names}
                # older checkpoints carried (dead) snapshots for
                # halo-carrying inputs too — drop them on the way in
                st["prev"] = {
                    k: self._place(self._lift(_tm(jnp.array, v)))
                    for k, v in sparse_state["prev"].items()
                    if k in names and specs[k].left_halo == 0}
                seed = sparse_state.get("seed") or {}
                if not isinstance(seed, dict):
                    # pre-policy-runner checkpoints (old KeyedEngine format)
                    # stored the solo hold seed as a bare (value, valid)
                    # tuple rather than a per-output dict
                    if not self.spec.solo:
                        raise ValueError(
                            "checkpoint hold seed is a bare tuple (single-"
                            "output format) but this runner serves a union "
                            "DAG with outputs "
                            f"{sorted(self.spec.out_precs)}")
                    seed = {"__out": seed}
                st["seed"] = {o: self._lift(_tm(jnp.array, v))
                              for o, v in seed.items()
                              if o in self.spec.out_precs}
                st["started"] = bool(sparse_state.get("started", True))
            # φ-init any halo-free snapshot the checkpoint didn't carry
            # (strict mode rejected this above): the next chunk's tick 0
            # then diffs against φ, the stream-start rule
            for name, (tv, tm) in self._tails.items():
                if specs[name].left_halo == 0 and name not in st["prev"]:
                    st["prev"][name] = (
                        _tm(lambda x: jnp.zeros((x.shape[0], 1)
                                                + x.shape[2:], x.dtype), tv),
                        jnp.zeros((tm.shape[0], 1), bool))
            self._sparse = st

    # -- late-data revision processing ---------------------------------------
    def enable_revision(self, horizon_chunks: int,
                        revise_bound: Optional[int] = None) -> None:
        """Keep a ring of the last ``horizon_chunks`` pre-chunk state
        snapshots (the :meth:`state` pytree), so sealed chunks inside the
        horizon can be revised through :meth:`revise` when late data
        patches their inputs.  ``revise_bound`` declares the maximum
        lateness (time units behind the newest stepped chunk) the ring is
        meant to cover; the ``revision`` analysis pass
        (:func:`repro.analysis.passes.pass_revision`) checks it against
        :meth:`repro.core.plan.ChangePlan.revision_horizon_chunks`.

        Enabling the ring trades the zero-sync steady state for
        revisability: every :meth:`step` round-trips the carried state to
        host once.  Hot paths that never see late data should leave this
        off (the 16-point policy lattice does, so the static passes and
        perf tests are unaffected)."""
        if horizon_chunks < 1:
            raise ValueError("horizon_chunks must be >= 1")
        self._rev_ring = collections.deque(maxlen=int(horizon_chunks))
        self.revision_horizon = int(horizon_chunks)
        self.revise_bound = (None if revise_bound is None
                             else int(revise_bound))

    def _revision_step(self):
        """The staged late-data revision step: ``step(tails, chunks,
        seg_dirty) -> (outs, new_tails)``.

        Like the fused sparse step, the compute is the per-shard compacted
        ``capacity_ladder`` switch (:meth:`_compute_local`) — never a
        dense chunk replay — but the dirty mask arrives as an argument
        (host-derived from :func:`repro.core.sparse.retro_segment_mask`
        over the patched tick times) instead of being diffed on device,
        and there is no hold fill: ChangePlan dilation proves every
        output outside the dirty segments unchanged, so only dirty
        segments' output ticks are read back (clean segments carry
        scatter residue)."""
        key = self._cache_key("revise")
        cache = self.spec.step_cache
        if key in cache:
            return cache[key]
        self.metrics.tracer.record_compile(self._compile_label(key))
        names, specs = self._names(), self.spec.input_specs
        K, n_segs, U = self._K, self.n_segs, self._U
        ladder = sparse_mod.capacity_ladder(U // self.policy.n_shards)
        branches = [self._compute_local(c) for c in ladder]
        caps = np.asarray(ladder, np.int32)

        def switched(w, *flat):
            with jax.named_scope("tilt.compact"):
                cnt = jnp.sum(w.astype(jnp.int32))
                b = jnp.searchsorted(jnp.asarray(caps), cnt, side="left")
            return jax.lax.switch(b, branches, w, *flat)

        sharded = self._shard_body(switched, len(names))

        def step(tails, chunks, seg_dirty):
            bufs, new_tails = {}, {}
            for name in names:
                s = specs[name]
                tv, tm = tails[name]
                cv, cm = chunks[name]
                fv = _tm(lambda a, b: jnp.concatenate([a, b], axis=1), tv, cv)
                fm = jnp.concatenate([tm, cm], axis=1)
                bufs[name] = (fv, fm)
                lo = s.core * n_segs
                new_tails[name] = (
                    _tm(lambda x: jax.lax.slice_in_dim(
                        x, lo, lo + s.left_halo, axis=1), fv),
                    jax.lax.slice_in_dim(fm, lo, lo + s.left_halo, axis=1))
            full = sharded(seg_dirty.reshape(U), *[bufs[nm] for nm in names])
            outs = {o: (_tm(lambda x: x.reshape(
                            (K, n_segs * x.shape[1]) + x.shape[2:]), fv),
                        fm.reshape(K, -1))
                    for o, (fv, fm) in full.items()}
            return outs, new_tails

        # the walked-forward tails are revision-owned (ring-entry copies,
        # then step outputs) — donate them like the chunk steps do
        return self._stage(key, step, "tilt_revision_step", donate=(0,))

    def revise(self, from_chunk: int, chunks, seg_dirty, *,
               commit: bool = True):
        """Re-run sealed chunks ``from_chunk .. from_chunk+len(chunks)-1``
        on patched inputs, computing only the flagged segments.

        ``chunks`` is one ``{input: SnapshotGrid}`` dict per revised chunk
        (the patched sealed grids, full chunk layout exactly as for
        :meth:`step`); ``seg_dirty`` one host bool mask per chunk, shaped
        ``(n_segs,)`` (single) or ``(n_keys, n_segs)`` (vmapped) —
        derived from :func:`repro.core.sparse.retro_segment_mask` over the
        patched tick times.  Returns one output result per chunk in
        :meth:`step`'s layout; only ticks inside dirty segments are
        meaningful (callers emit corrections for those segments only —
        see :class:`repro.ingest.IngestRunner`).

        With ``commit=True`` (required to keep live state consistent) the
        revision must extend through the newest stepped chunk; the
        walked-forward tails then replace the live carried tails, the
        change state goes conservative (all-dirty tails — a superset of
        true dirtiness, still bit-exact by the sparse exactness
        contract), and ring entries passed en route are refreshed with
        the patched tails so later revisions restore patched history.
        ``commit=False`` is a read-only what-if replay."""
        if self._rev_ring is None:
            raise ValueError(
                "revision disabled — call enable_revision() first")
        if len(chunks) != len(seg_dirty):
            raise ValueError("one seg_dirty mask per revised chunk required")
        span = self.n_segs * self.spec.span
        cur = self._t // span
        if commit and from_chunk + len(chunks) != cur:
            raise ValueError(
                f"commit=True revisions must extend through the newest "
                f"stepped chunk {cur - 1} (got chunks {from_chunk}.."
                f"{from_chunk + len(chunks) - 1})")
        entry = next((e for e in self._rev_ring
                      if e["chunk"] == from_chunk), None)
        if entry is None:
            have = sorted(e["chunk"] for e in self._rev_ring)
            raise ValueError(
                f"no state snapshot for chunk {from_chunk} in the revision "
                f"ring (have {have}) — the patch is beyond the horizon")
        st, specs, K = entry["state"], self.spec.input_specs, self._K

        step = self._revision_step()
        tails = None
        results = []
        n_units = 0
        last_in = last_sd = last_outs = None
        for i, (ch, sd) in enumerate(zip(chunks, seg_dirty)):
            chunk_in = self._ingest(ch)
            if tails is None:
                tails = {}
                for name in self._names():
                    if name in st:
                        # jnp.array (copy): the ring entry stays intact and
                        # the donating revision step never consumes it
                        tails[name] = self._place(
                            self._lift(_tm(jnp.array, st[name])))
                    else:  # pre-stream snapshot: φ tails (the restore rule)
                        hl = specs[name].left_halo
                        cv, cm = chunk_in[name]
                        tails[name] = self._place((
                            _tm(lambda x: jnp.zeros(
                                (K, hl) + x.shape[2:], x.dtype), cv),
                            jnp.zeros((K, hl), bool)))
            else:
                # the ring entry for this chunk captured pre-patch tails —
                # refresh it with the walked (patched) ones so a later
                # revision restoring from here sees patched history
                for e in self._rev_ring:
                    if e["chunk"] == from_chunk + i:
                        for name in self._names():
                            e["state"][name] = _tm(
                                np.asarray, self._strip(tails[name]))
            sd = np.asarray(sd, bool).reshape(K, self.n_segs)
            n_units += int(sd.sum())
            outs, tails = step(tails, chunk_in,
                               self._unit_flags(jnp.asarray(sd)))
            if self.metrics.on:
                self._m_dispatches.add(1)
            last_in, last_sd, last_outs = chunk_in, sd, outs
            res = {}
            for o, (v, m) in self._postprocess(outs).items():
                res[o] = SnapshotGrid(value=v, valid=m,
                                      t0=(from_chunk + i) * span,
                                      prec=self.spec.out_precs[o])
            results.append(res["__out"] if self.spec.solo else res)

        if commit and chunks:
            self._tails = tails
            if self._sparse is not None:
                stt = self._sparse
                ld = jnp.asarray(last_sd[:, -1])
                for name in self._names():
                    hl = specs[name].left_halo
                    if hl:
                        # conservative: the patched tail is marked fully
                        # dirty — dirtiness only ever widens, and extra
                        # computed segments are bit-identical by the
                        # sparse exactness contract
                        stt["dirty"][name] = self._place(
                            jnp.ones((K, hl), bool))
                    else:
                        cv, cm = last_in[name]
                        stt["prev"][name] = (_tm(lambda x: x[:, -1:], cv),
                                             cm[:, -1:])
                for o, (sv, sm) in list(stt["seed"].items()):
                    ov, om = last_outs[o]
                    stt["seed"][o] = (
                        _tm(lambda x, s: jnp.where(_bc(ld, x[:, -1]),
                                                   x[:, -1], s), ov, sv),
                        jnp.where(ld, om[:, -1], sm))
        if self.metrics.on:
            self._m_rev_runs.add(1)
            self._m_rev_chunks.add(len(chunks))
            self._m_rev_units.add(n_units)
        return results
