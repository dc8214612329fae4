#!/usr/bin/env python3
"""Bring-up smoke of the served stream path on a TPU.

Drives the TiLT paper's keyed fraud-detection deployment through the
entry points a user calls (``repro.serve.build_service`` ->
``ServeLoop.serve`` for the chunk path; the admission ring ->
``IngestRunner`` for the event path) and checks every output against the
plain reference, the numpy ``spe/eventspe.py`` pipeline the app carries.

    python chip_smoke.py              # one chip: sparse + dense bodies,
                                      # event path with disorder
    python chip_smoke.py --chips 4    # four chips: mesh placement only,
                                      # bit for bit against one chip

Every phase runs in this one process (a chip belongs to one process).  The
script exits non-zero, without the result line, when JAX finds no TPU or
when any phase fails.  The figures it prints (compile seconds, host wall
seconds, events) are smoke figures on the host clock, not device metrics.
The last line is ``{"ok": true, "device": {...}}``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax keeps its compile cache
there; this script sets no cache directory of its own.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# value tolerance against the float64 reference, and the band around the
# fraud threshold inside which a flag may flip (f32 vs f64 rounding)
RTOL = 1e-4
ATOL = 1e-2

# share of the event path's events delayed (inside the lateness bound)
LATE_FRAC = 0.03


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one run.  The defaults are the deployment every run of
    this script serves (:data:`DEPLOYMENT`); only tests build others."""
    keys: int = 4096
    win: int = 1000
    seg: int = 256          # output ticks per segment
    segs: int = 8           # segments per chunk
    chunks: int = 8
    sample: int = 64        # keys checked against the reference
    event_keys: int = 64
    event_chunks: int = 3
    lateness: int = 64

    @property
    def span(self) -> int:
        """Ticks per key per chunk."""
        return self.seg * self.segs


DEPLOYMENT = Sizes()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require_tpu(chips: int):
    """The device check every run starts with: a TPU, ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: {chips} TPU devices required, "
                         f"found {len(devs)}")
    return devs


# -- data --------------------------------------------------------------------

def fraud_app(win: int):
    from repro.data.apps import make_keyed_app
    return make_keyed_app("fraud", win=win)


def keyed_stream(app, n_keys: int, n_ticks: int, seed: int,
                 integer: bool = False):
    """(value f32 (K, T), valid bool (K, T)) from the app's own keyed
    generator (lognormal amounts, injected fraud, ~30% idle keys per
    tick).  ``integer`` rounds amounts to small integers, so that float
    sums are exact and any two partitionings must agree bit for bit."""
    d = app.make_keyed_input(n_keys, n_ticks, seed)["in"]
    value = d["value"]
    if integer:
        value = np.minimum(np.round(value), 120.0)
    return value.astype(np.float32), np.asarray(d["valid"], bool)


def chunks_of(value, valid, span: int, n_chunks: int, keyed: bool = True):
    """Host numpy chunk grids (the serving loop does the H2D puts)."""
    from repro.core.stream import SnapshotGrid
    for c in range(n_chunks):
        sl = np.s_[:, c * span:(c + 1) * span] if keyed else \
            np.s_[c * span:(c + 1) * span]
        yield {"in": SnapshotGrid(value=value[sl], valid=valid[sl],
                                  t0=c * span, prec=1)}


# -- reference ---------------------------------------------------------------

def reference(app, value, valid, keys, batch: int):
    """The eventspe pipeline per key: ``{key: (excess f64, flag bool)}``
    over every tick (output tick i is the event at ts = i + 1)."""
    from repro.spe import eventspe as es
    T = value.shape[1]
    ts = np.arange(1, T + 1, dtype=np.int64)
    out = {}
    for k in keys:
        x = value[k].astype(np.float64)
        batches = (es.Batch(ts[i:i + batch], x[i:i + batch],
                            valid[k, i:i + batch])
                   for i in range(0, T, batch))
        res = app.spe.run(batches)
        ev = np.concatenate([np.asarray(b.value, np.float64) for b in res])
        ok = np.concatenate([b.valid for b in res])
        rts = np.concatenate([b.ts for b in res])
        assert np.array_equal(rts, ts), "reference emitted off-grid events"
        out[int(k)] = (ev, ok)
    return out


def check_against_reference(got, ref, value, what: str) -> dict:
    """Flags must agree except within the tolerance band around the
    threshold; values of agreeing flags within ``ATOL + RTOL·scale``."""
    n_flags = n_flips = 0
    worst = 0.0
    for k, (ev, ok) in ref.items():
        gv, gm = got[k]
        x = value[k].astype(np.float64)
        tol = ATOL + RTOL * (np.abs(x) + np.abs(x - ev))
        band = np.abs(ev) <= tol
        bad = (gm != ok) & ~band
        if bad.any():
            i = int(np.flatnonzero(bad)[0])
            raise AssertionError(
                f"{what}: key {k} tick {i}: flag {bool(gm[i])} vs reference "
                f"{bool(ok[i])} (excess {ev[i]!r}, tol {tol[i]!r})")
        both = gm & ok
        err = np.abs(gv.astype(np.float64) - ev)
        if (err[both] > tol[both]).any():
            i = int(np.flatnonzero(both & (err > tol))[0])
            raise AssertionError(
                f"{what}: key {k} tick {i}: excess {gv[i]!r} vs reference "
                f"{ev[i]!r} (tol {tol[i]!r})")
        if both.any():
            worst = max(worst, float((err[both] / tol[both]).max()))
        n_flags += int(ok.sum())
        n_flips += int((gm != ok).sum())
    return {"keys": len(ref), "ref_flags": n_flags, "band_flips": n_flips,
            "worst_err_over_tol": worst}


# -- chunk path ----------------------------------------------------------------

def build(app, sz: Sizes, policy, n_keys):
    from repro.serve import build_service
    t0 = time.perf_counter()
    svc = build_service(app.query, out_len=sz.seg, policy=policy,
                        n_keys=n_keys, segs_per_chunk=sz.segs)
    return svc, time.perf_counter() - t0


def kernel_in_sparse_step(svc) -> bool:
    """Whether the AOT-compiled steady sparse step holds a Pallas kernel
    (``tpu_custom_call`` in its optimized HLO): proof that change
    detection runs as the kernel, not an XLA fallback."""
    runner = svc.runner
    key = dict(runner.aot_keys())["sparse_fused(steady)"]
    return "tpu_custom_call" in runner.spec.step_cache[key].as_text()


def serve_chunks(svc, value, valid, span, n_chunks, rows=None,
                 keyed=True):
    """Serve every chunk; gather output rows ``rows`` (all when None) to
    the host.  Returns ``(value, valid, host wall seconds per chunk — the
    served call plus the gather —, the last output's sharding)``."""
    import jax.numpy as jnp
    idx = None if rows is None else jnp.asarray(rows)
    vs, ms, walls = [], [], []
    t0 = time.perf_counter()
    for out in svc.serve(chunks_of(value, valid, span, n_chunks, keyed)):
        v, m = out.value, out.valid
        sharding = v.sharding
        if idx is not None:
            v, m = v[idx], m[idx]
        vs.append(np.asarray(v))
        ms.append(np.asarray(m))
        walls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
    return (np.concatenate(vs, axis=-1), np.concatenate(ms, axis=-1), walls,
            sharding)


def phase_keyed(app, sz: Sizes, body, value, valid, sample, ref):
    import jax
    from repro.engine import ExecPolicy
    K = value.shape[0]
    svc, compile_s = build(app, sz, ExecPolicy(body=body, keys="vmapped"), K)
    kernel = kernel_in_sparse_step(svc) if body == "sparse" else None
    if body == "sparse" and jax.default_backend() == "tpu":
        assert kernel, "seg_dirty kernel missing from the sparse step HLO"
    gv, gm, wall, _ = serve_chunks(svc, value, valid, sz.span, sz.chunks,
                                   rows=sample)
    stats = check_against_reference(
        {int(k): (gv[i], gm[i]) for i, k in enumerate(sample)}, ref, value,
        f"keyed {body}")
    events = int(valid[:, :sz.span * sz.chunks].sum())
    say(f"phase=keyed body={body} keys={K} chunks={sz.chunks} "
        f"ticks_per_chunk={sz.span} win={sz.win} events={events} "
        f"compile_s={compile_s!r} chunk_wall_s={wall!r} "
        f"plan={svc.plan_source} aot={svc.aot_report} "
        f"sparse_step_kernel={kernel} reference={stats}")
    return stats


# -- event path ----------------------------------------------------------------

def arrival_order(valid, late_frac, lateness, rng):
    """(tick, key) events of every valid tick in time order, a fraction
    of them delayed by up to ``lateness // 2`` ticks: out of order, but
    within the lateness bound."""
    t, k = np.nonzero(valid.T)
    delay = np.where(rng.random(t.size) < late_frac,
                     rng.integers(1, max(2, lateness // 2), t.size), 0)
    order = np.lexsort((k, t + delay))
    return t[order], k[order], int((delay > 0).sum())


def phase_events(app, sz: Sizes, seed: int, rng):
    from repro.core.stream import Event
    from repro.engine import ExecPolicy
    from repro.serve import build_service
    K = sz.event_keys
    value, valid = keyed_stream(app, K, sz.span * sz.event_chunks, seed + 1)
    t0 = time.perf_counter()
    svc = build_service(app.query, out_len=sz.seg,
                        policy=ExecPolicy(body="sparse", keys="vmapped"),
                        n_keys=K, segs_per_chunk=sz.segs)
    compile_s = time.perf_counter() - t0
    svc.attach_events(lateness=sz.lateness, policy="drop",
                      capacity=1 << 16, shed="block")
    ts, ks, n_late = arrival_order(valid, LATE_FRAC, sz.lateness, rng)
    sealed = []
    t0 = time.perf_counter()
    for i, (t, k) in enumerate(zip(ts.tolist(), ks.tolist())):
        svc.offer("in", Event(t, t + 1, float(value[k, t])), key=k)
        if (i + 1) % 8192 == 0:
            sealed += svc.pump()[0]
    sealed += svc.finish()[0]
    wall = time.perf_counter() - t0
    snap = svc.runner.metrics.snapshot()["counters"]
    late = snap["ingest.late_events"]["value"]
    assert late == 0, f"{late} events fell behind the sealed frontier"
    sealed.sort(key=lambda s: s.chunk)
    assert [s.chunk for s in sealed] == list(range(sz.event_chunks)), \
        [s.chunk for s in sealed]
    gv = np.concatenate([np.asarray(s.outputs.value) for s in sealed], 1)
    gm = np.concatenate([np.asarray(s.outputs.valid) for s in sealed], 1)
    ref = reference(app, value, valid, range(K), sz.span)
    stats = check_against_reference(
        {k: (gv[k], gm[k]) for k in range(K)}, ref, value, "event path")
    say(f"phase=events keys={K} chunks={sz.event_chunks} "
        f"events={ts.size} out_of_order={n_late} lateness={sz.lateness} "
        f"compile_s={compile_s!r} ingest_wall_s={wall!r} "
        f"reference={stats}")
    return stats


# -- four chips: mesh placement vs one chip -------------------------------------

def _check_spread(x, n: int, sharded: bool, what: str) -> None:
    """``x`` lives on all ``n`` devices: split over them (``sharded``, one
    equal shard each) or a full copy on every one.  Catches state or
    outputs that all land on device 0."""
    sh = x.sharding
    assert len(sh.device_set) == n, f"{what} on {len(sh.device_set)} of {n}"
    if sharded and n > 1:
        assert not sh.is_fully_replicated, f"{what} replicated, not split"
        shapes = {s.data.shape for s in x.addressable_shards}
        assert shapes == {sh.shard_shape(x.shape)} and \
            sh.shard_shape(x.shape)[0] * n == x.shape[0], (what, shapes)
    elif not sharded:
        assert sh.is_fully_replicated, f"{what} not replicated: {sh}"


def _check_split(sh, n: int, what: str) -> None:
    """A sharding that splits its array over all ``n`` devices."""
    assert len(sh.device_set) == n and \
        (n == 1 or not sh.is_fully_replicated), f"{what}: {sh}"


def _compare(res, what: str) -> dict:
    (mv, mm), (lv, lm) = res["mesh"], res["local"]
    assert np.array_equal(mm, lm), f"{what}: mesh vs local validity differs"
    assert np.array_equal(mv[mm], lv[lm]), \
        f"{what}: mesh vs local values differ"
    return {"flags": int(mm.sum()), "identical": True}


def phase_mesh_keyed(app, sz: Sizes, seed: int, devs):
    """Keys sharded 4 ways (``placement=mesh``, ``keys='vmapped'``) vs the
    same stream on one chip: every output bit-identical.  Outputs and
    carried tails are split over the devices by key."""
    from jax.sharding import Mesh
    from repro.engine import ExecPolicy
    n = len(devs)
    K = sz.keys
    value, valid = keyed_stream(app, K, sz.span * sz.chunks, seed,
                                integer=True)
    mesh = Mesh(np.asarray(devs), ("data",))
    res = {}
    for name, placement in (("mesh", mesh), ("local", "local")):
        pol = ExecPolicy(body="sparse", keys="vmapped", placement=placement)
        svc, compile_s = build(app, sz, pol, K)
        gv, gm, wall, out_sh = serve_chunks(svc, value, valid, sz.span,
                                            sz.chunks)
        if name == "mesh":
            _check_split(out_sh, n, "outputs")
            _check_spread(svc.runner._tails["in"][0], n, True, "value tail")
            _check_spread(svc.runner._tails["in"][1], n, True, "valid tail")
        res[name] = (gv, gm)
        say(f"phase=mesh_keyed placement={name} keys={K} "
            f"chunks={sz.chunks} compile_s={compile_s!r} "
            f"chunk_wall_s={wall!r}")
    return _compare(res, "mesh keyed")


def phase_mesh_single(sz: Sizes, devs, value, valid):
    """One stream, its segments sharded 4 ways (``keys='single'``): the
    window spans shards, read from the replicated chunk buffer.  Outputs
    are split over the devices by segment; the carried tails, which every
    shard's window reads, are a full copy on each device."""
    from jax.sharding import Mesh
    from repro.data.apps import make_app
    from repro.engine import ExecPolicy
    n = len(devs)
    app = make_app("fraud", win=sz.win)
    mesh = Mesh(np.asarray(devs), ("data",))
    res = {}
    for name, placement in (("mesh", mesh), ("local", "local")):
        pol = ExecPolicy(body="sparse", keys="single", placement=placement)
        svc, compile_s = build(app, sz, pol, None)
        gv, gm, _, out_sh = serve_chunks(svc, value, valid, sz.span,
                                         sz.chunks, keyed=False)
        if name == "mesh":
            _check_split(out_sh, n, "outputs")
            _check_spread(svc.runner._tails["in"][0], n, False, "value tail")
            _check_spread(svc.runner._tails["in"][1], n, False, "valid tail")
        res[name] = (gv, gm)
        say(f"phase=mesh_single placement={name} chunks={sz.chunks} "
            f"compile_s={compile_s!r} out_sharding={out_sh}")
    return _compare(res, "mesh single")


def phase_halo(sz: Sizes, devs, value, valid):
    """Time sharded 4 ways one-shot (``parallel.shard_map_run``): each
    shard's core is shorter than the window, so its left halo arrives
    over the multi-hop ppermute chain of ``core/halo.py``."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import compile as qc
    from repro.core.parallel import partition_run, shard_map_run
    from repro.core.stream import SnapshotGrid
    from repro.data.apps import make_app
    n = len(devs)
    app = make_app("fraud", win=sz.win)
    core = sz.span // n
    T = core * n
    exe = qc.compile_query(app.query.node, out_len=core)
    hops = -(-exe.input_specs["in"].left_halo // core)
    assert hops >= 2, f"window {sz.win} over {core}-tick cores: 1 hop"
    g = {"in": SnapshotGrid(value=jnp.asarray(value[:T]),
                            valid=jnp.asarray(valid[:T]), t0=0, prec=1)}
    mesh = Mesh(np.asarray(devs), ("data",))
    t0 = time.perf_counter()
    sh = shard_map_run(exe, g, mesh, axis="data")
    sv, sm = np.asarray(sh.value), np.asarray(sh.valid)
    wall = time.perf_counter() - t0
    _check_spread(sh.value, n, True, "sharded output")
    loc = partition_run(exe, g, 0, n)
    lv, lm = np.asarray(loc.value), np.asarray(loc.valid)
    assert np.array_equal(sm, lm), "sharded vs local validity differs"
    assert np.array_equal(sv[sm], lv[lm]), "sharded vs local values differ"
    say(f"phase=halo shards={n} core={core} win={sz.win} hops={hops} "
        f"wall_s={wall!r}")
    return {"flags": int(sm.sum()), "hops": hops, "identical": True}


# -- drivers -------------------------------------------------------------------

def reduced_line(sz: Sizes, chips: int) -> str:
    """Every cut this run makes to the deployment's scale."""
    if chips == 4:
        cuts = ["amounts rounded to integers <= 120 (exact float sums, so "
                "mesh vs one chip is compared bit for bit)",
                f"halo phase: one stream of {sz.span} ticks, one shot"]
    else:
        cuts = [f"reference checked on {min(sz.sample, sz.keys)} of "
                f"{sz.keys} keys",
                f"event path: {sz.event_keys} keys x {sz.event_chunks} "
                f"chunks ({sz.event_keys * sz.span * sz.event_chunks} "
                f"ticks), python-rate per-event admission"]
    return f"reduced: stream of {sz.chunks} chunks x {sz.span} ticks/key; " \
        + "; ".join(cuts)


def run_one(sz: Sizes, seed: int) -> dict:
    """One chip: the keyed deployment under both bodies, then the event
    path, each against the reference."""
    app = fraud_app(sz.win)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    value, valid = keyed_stream(app, sz.keys, sz.span * sz.chunks, seed)
    sample = np.sort(rng.choice(sz.keys, min(sz.sample, sz.keys),
                                replace=False))
    ref = reference(app, value, valid, sample, sz.span)
    say(f"setup: keys={sz.keys} ticks={value.shape[1]} "
        f"chunk_bytes={value[:, :sz.span].nbytes + valid[:, :sz.span].nbytes}"
        f" sample={len(sample)} data+reference_wall_s="
        f"{time.perf_counter() - t0!r}")
    out = {body: phase_keyed(app, sz, body, value, valid, sample, ref)
           for body in ("sparse", "dense")}
    out["events"] = phase_events(app, sz, seed, rng)
    return out


def run_four(sz: Sizes, seed: int, devs) -> dict:
    """Four chips: only the mesh placements and what they are compared
    with, bit for bit, on integer-valued amounts."""
    app = fraud_app(sz.win)
    out = {"mesh_keyed": phase_mesh_keyed(app, sz, seed, devs)}
    value, valid = keyed_stream(app, 1, sz.span * sz.chunks, seed + 2,
                                integer=True)
    out["mesh_single"] = phase_mesh_single(sz, devs, value[0], valid[0])
    out["halo"] = phase_halo(sz, devs, value[0], valid[0])
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    devs = require_tpu(args.chips)
    import jax
    d = devs[0]
    sz = DEPLOYMENT
    say(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    say("figures are smoke figures on the host clock, not device metrics")
    say(f"deployment: {dataclasses.asdict(sz)} late_frac={LATE_FRAC} "
        f"seed={args.seed}")
    say(reduced_line(sz, args.chips))
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four(sz, args.seed, devs[:4])
    else:
        run_one(sz, args.seed)
    say(f"all phases passed; total_wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
