"""Change-compressed sparse execution tests (repro.core.sparse).

The whole subsystem rests on one invariant: sparse ≡ dense **bit-for-bit**
on integer-valued data (same partitioning ⇒ identical float association;
see the float caveat in repro/multiquery/__init__.py), whatever the change
pattern — including the all-clean and all-dirty extremes, dirty spans that
cross partition/chunk boundaries, chunked execution with carried change
state (SparseStreamRunner, KeyedEngine sparse mode) and explicit
change-event channels.  The shard_map comparison lives in
tests/test_parallel_multidev.py (needs a multi-device subprocess).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile as qc
from repro.core import sparse as sp
from repro.core.frontend import TStream
from repro.core.parallel import (SparseStreamRunner, StreamRunner,
                                 partition_run)
from repro.core.stream import SnapshotGrid
from repro.engine import KeyedEngine, keyed_grid

N = 512


def pw_const(n, rate, seed, invalid_spans=()):
    """Piecewise-constant integer-valued stream: ``rate`` of ticks change;
    ``invalid_spans`` are (start, stop) φ gaps (validity changes count as
    changes too)."""
    rng = np.random.default_rng(seed)
    change = rng.random(n) < rate
    change[0] = True
    raw = np.floor(rng.random(n) * 100).astype(np.float32)
    idx = np.maximum.accumulate(np.where(change, np.arange(n), -1))
    vals = raw[idx]
    valid = np.ones(n, bool)
    for a, b in invalid_spans:
        valid[a:b] = False
    return vals, valid


def _grid(vals, valid, t0=0, prec=1):
    return SnapshotGrid(value=jnp.asarray(vals), valid=jnp.asarray(valid),
                        t0=t0, prec=prec)


def _assert_same(ref, got, ctx=""):
    m1, m2 = np.asarray(ref.valid), np.asarray(got.valid)
    assert np.array_equal(m1, m2), (ctx, m1.sum(), m2.sum())
    r, g = ref.value, got.value
    if isinstance(r, dict):
        for k in r:
            assert np.array_equal(np.asarray(r[k])[m1],
                                  np.asarray(g[k])[m1]), (ctx, k)
    else:
        assert np.array_equal(np.asarray(r)[m1], np.asarray(g)[m1]), ctx


# query zoo: (name, builder, segment out_len) — spans window/strided/shift/
# φ-aware/interp shapes so dirtiness dilation is exercised per edge rule
def _trend(s):
    return (s.window(16).mean()
            .join(s.window(32).mean(), lambda a, b: a - b)
            .where(lambda d: d > 0))


def _tumbling(s):
    return s.window(8, stride=8).sum()


def _shifted(s):
    return s.join(s.shift(3), lambda a, b: a - b)


def _coalesce_const(s):
    return s.coalesce(TStream.const(5.0))


def _interp(s):
    return s.interpolate(mode="linear", max_gap=8)  # lookahead query


QUERIES = {
    "trend": (_trend, 32),
    "tumbling": (_tumbling, 8),     # out_prec 8 -> span 64
    "shifted": (_shifted, 32),
    "coalesce_const": (_coalesce_const, 32),
    "interp": (_interp, 32),
}


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_sparse_run_bit_identical_to_partition_run(name):
    fn, out_len = QUERIES[name]
    q = fn(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=out_len, pallas=False,
                           sparse=True)
    n_parts = N // (out_len * exe.out_prec)
    # bursty change pattern: value changes at {77, 78, 305}, φ gap
    # (100, 130) — most of the timeline holds, so every query shape must
    # leave some segments clean
    vals = np.full(N, 6.0, np.float32)
    vals[77] = 13.0
    vals[78:] = 2.0
    vals[305:] = 9.0
    valid = np.ones(N, bool)
    valid[100:130] = False
    g = {"in": _grid(vals, valid)}
    ref = partition_run(exe, g, 0, n_parts)
    got = sp.sparse_run(exe, g, 0, n_parts)
    _assert_same(ref, got, name)
    # the sparse path must actually compact on this ~2%-change stream
    n_dirty = int(np.asarray(sp.segment_mask(exe, g, 0, n_parts)).sum())
    assert n_dirty < n_parts, (name, n_dirty, n_parts)


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_fused_run_bit_identical_to_three_phase(name):
    """The fused single-jit path (kernel mask + device-resident bucket pick
    + switch) must reproduce the three-phase staged path — the semantics of
    record — bit-for-bit, at a compacting change rate AND at the all-dirty
    extreme (which exercises the dense-all full-capacity switch branch
    against the staged gather/scatter/hold body)."""
    fn, out_len = QUERIES[name]
    q = fn(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=out_len, pallas=False,
                           sparse=True)
    n_parts = N // (out_len * exe.out_prec)
    for rate, seed in ((0.02, 3), (1.0, 5)):
        vals, valid = pw_const(N, rate, seed, invalid_spans=((40, 70),))
        g = {"in": _grid(vals, valid)}
        got = sp.sparse_run(exe, g, 0, n_parts, fused=True)
        ref = sp.sparse_run(exe, g, 0, n_parts, fused=False)
        _assert_same(ref, got, f"{name} rate={rate}")


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_segment_mask_pallas_kernel_matches_staged(name):
    """The fused change-detection kernel (interpret mode on CPU) resolves
    the same per-segment dirty flags as the staged source_dirty +
    seg_ranges + range_any reference, across the query zoo's dilation
    shapes (window, strided output, shift, lookahead interp)."""
    fn, out_len = QUERIES[name]
    q = fn(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=out_len, pallas=False,
                           sparse=True)
    n_parts = N // (out_len * exe.out_prec)
    vals, valid = pw_const(N, 0.03, seed=17, invalid_spans=((200, 230),))
    g = {"in": _grid(vals, valid)}
    staged = np.asarray(sp.segment_mask(exe, g, 0, n_parts))
    kernel = np.asarray(sp.segment_mask(exe, g, 0, n_parts, pallas=True))
    oracle = np.asarray(sp.segment_mask(exe, g, 0, n_parts, pallas=False))
    assert np.array_equal(staged, kernel), (name, staged, kernel)
    assert np.array_equal(staged, oracle), (name, staged, oracle)


def test_strided_output_dilation_covers_stride_gap():
    """Regression: with out_prec > input prec the hold rule compares ticks
    one *output stride* apart, so the dilation must widen by
    ``out_prec − prec`` — a change landing just before a segment's lineage
    bound (tick 60 here) must still dirty the following segment."""
    q = _tumbling(TStream.source("in", prec=1))  # window 8, stride 8
    exe = qc.compile_query(q.node, out_len=8, pallas=False, sparse=True)
    n_parts = 256 // 64
    for pos in (57, 60, 63, 64):  # straddle the 8-wide stride gap
        vals = np.full(256, 3.0, np.float32)
        vals[pos:] = 8.0
        g = {"in": _grid(vals, np.ones(256, bool))}
        _assert_same(partition_run(exe, g, 0, n_parts),
                     sp.sparse_run(exe, g, 0, n_parts), f"pos={pos}")


def test_lookahead_grid_end_is_a_virtual_change():
    """Regression: the supplied grid's end flips lookahead lineages to φ;
    trailing outputs must compute (dense yields φ there), not hold the
    last valid value."""
    q = TStream.source("in", prec=1).shift(-5)
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    vals = np.full(256, 3.0, np.float32)  # fully constant: no real changes
    g = {"in": _grid(vals, np.ones(256, bool))}
    ref = partition_run(exe, g, 0, 8)
    got = sp.sparse_run(exe, g, 0, 8)
    assert not np.asarray(ref.valid)[-5:].any()  # dense: trailing φ
    _assert_same(ref, got, "grid-end")


def test_sparse_all_clean_and_all_dirty_extremes():
    q = _trend(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    # all-clean: constant stream — only the forced-dirty stream-start tick
    # (and its dilation into the next segment) computes
    g = {"in": _grid(np.full(N, 7.0, np.float32), np.ones(N, bool))}
    mask = np.asarray(sp.segment_mask(exe, g, 0, N // 32))
    assert mask[0] and not mask[2:].any(), mask.astype(int)
    _assert_same(partition_run(exe, g, 0, N // 32),
                 sp.sparse_run(exe, g, 0, N // 32), "all-clean")
    # all-dirty: every tick changes — every segment computes
    vals, valid = pw_const(N, 1.0, seed=5)
    g = {"in": _grid(vals, valid)}
    assert np.asarray(sp.segment_mask(exe, g, 0, N // 32)).all()
    _assert_same(partition_run(exe, g, 0, N // 32),
                 sp.sparse_run(exe, g, 0, N // 32), "all-dirty")


def test_dirty_span_crosses_partition_boundary():
    """A change just before a partition boundary dirties the *next*
    partition too (its lookback window reaches across); outputs must match
    dense and the dilation must be visible in the segment mask."""
    q = _trend(TStream.source("in", prec=1))  # lookback 32
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    vals = np.full(N, 4.0, np.float32)
    vals[95:] = 9.0  # change at tick 95: dirties segments 2 (64..95) and 3+
    g = {"in": _grid(vals, np.ones(N, bool))}
    mask = np.asarray(sp.segment_mask(exe, g, 0, N // 32))
    assert mask[2] and mask[3], mask.astype(int)  # span crosses 96-boundary
    # beyond the change's 32-tick lookback reach, segments stay clean
    assert not mask[4:].any(), mask.astype(int)
    _assert_same(partition_run(exe, g, 0, N // 32),
                 sp.sparse_run(exe, g, 0, N // 32), "boundary")


def test_sparse_stream_runner_matches_dense_chunked():
    """Chunked sparse execution with carried change state ≡ the dense
    StreamRunner on the same chunking, including an all-clean middle chunk
    and a change in the last ticks of a chunk (the carried dirty tail must
    dirty the next chunk's leading segment)."""
    q = _trend(TStream.source("in", prec=1))
    exe_s = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    exe_d = qc.compile_query(q.node, out_len=32, pallas=False)

    vals = np.full(N, 3.0, np.float32)
    vals[127:] = 8.0   # last tick of chunk 0 (chunks of 128): dirty tail
    vals[300:] = 2.0   # mid chunk 2
    valid = np.ones(N, bool)

    dense = StreamRunner(exe_d)
    runner = SparseStreamRunner(exe_s, segs_per_chunk=4)
    got_v, got_m, ref_v, ref_m = [], [], [], []
    for c in range(4):
        sl = slice(c * 128, (c + 1) * 128)
        chunk = _grid(vals[sl], valid[sl], t0=c * 128)
        o = runner.step({"in": chunk})
        got_v.append(np.asarray(o.value))
        got_m.append(np.asarray(o.valid))
        for k in range(4):  # dense runner steps one 32-tick partition
            ssl = slice(c * 128 + k * 32, c * 128 + (k + 1) * 32)
            od = dense.step({"in": _grid(vals[ssl], valid[ssl])})
            ref_v.append(np.asarray(od.value))
            ref_m.append(np.asarray(od.valid))
    gm, rm = np.concatenate(got_m), np.concatenate(ref_m)
    gv, rv = np.concatenate(got_v), np.concatenate(ref_v)
    assert np.array_equal(gm, rm)
    assert np.array_equal(gv[rm], rv[rm])


def test_sparse_stream_runner_checkpoint_resume_bit_identical():
    q = _trend(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    vals, valid = pw_const(N, 0.05, seed=11)

    r1 = SparseStreamRunner(exe, segs_per_chunk=4)
    r1.step({"in": _grid(vals[:128], valid[:128])})
    state = r1.state()

    r2 = SparseStreamRunner(exe, segs_per_chunk=4)
    r2.restore(state)
    a = r1.step({"in": _grid(vals[128:256], valid[128:256])})
    b = r2.step({"in": _grid(vals[128:256], valid[128:256])})
    assert a.t0 == b.t0 == 128
    assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
    assert np.array_equal(np.asarray(a.value), np.asarray(b.value))


def test_keyed_engine_sparse_matches_dense():
    """Key-axis compaction: engines with mostly-idle keys must agree with
    dense keyed execution bit-for-bit, and only small compaction buckets
    may ever have been compiled."""
    K, T, P = 32, 256, 4
    rng = np.random.default_rng(2)
    vals = np.zeros((K, T), np.float32)
    valid = np.zeros((K, T), bool)
    for k in range(0, K, 4):  # 1 in 4 keys active
        v, m = pw_const(T, 0.03, seed=k)
        vals[k], valid[k] = v, m
    q = _trend(TStream.source("in", keyed=True))
    exe_d = qc.compile_query(q.node, out_len=T // P, pallas=False)
    exe_s = qc.compile_query(q.node, out_len=T // P, pallas=False,
                             sparse=True)
    g = {"in": keyed_grid(vals, valid)}
    ref = KeyedEngine(exe_d, n_keys=K).run(g, P)
    eng = KeyedEngine(exe_s, n_keys=K, sparse=True)
    got = eng.run(g, P)
    _assert_same(ref, got, "keyed")
    # after the forced-dense first step, later steps compact to <= 16 keys
    # (the staged compute steps live in the unified runner's cache, keyed
    # ("compute", ..., capacity))
    caps = sorted(k[-1] for k in exe_s._runner_step_cache
                  if isinstance(k, tuple) and k[0] == "compute")
    assert caps and caps[0] <= K // 2, caps


def test_keyed_engine_sparse_checkpoint_resume_bit_identical():
    K, T = 16, 128
    rng = np.random.default_rng(4)
    vals = np.stack([pw_const(T, 0.05, seed=k)[0] for k in range(K)])
    valid = np.ones((K, T), bool)
    q = _trend(TStream.source("in", keyed=True))
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)

    def chunk(j):
        sl = slice(j * 32, (j + 1) * 32)
        return {"in": keyed_grid(vals[:, sl], valid[:, sl], t0=j * 32)}

    e1 = KeyedEngine(exe, n_keys=K, sparse=True)
    e1.step(chunk(0))
    e1.step(chunk(1))
    state = e1.state()
    e2 = KeyedEngine(exe, n_keys=K, sparse=True)
    e2.restore(state)
    a = e1.step(chunk(2))
    b = e2.step(chunk(2))
    assert a.t0 == b.t0
    assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
    assert np.array_equal(np.asarray(a.value), np.asarray(b.value))


def test_explicit_change_channel_overrides_diff():
    """An explicit change-event channel replaces the value diff: the true
    change mask reproduces the auto result; an all-true mask degrades to
    dense (all segments dirty) with identical output."""
    q = _trend(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=32, pallas=False, sparse=True)
    rng = np.random.default_rng(9)
    change = rng.random(N) < 0.02
    change[0] = True
    raw = np.floor(rng.random(N) * 100).astype(np.float32)
    vals = raw[np.maximum.accumulate(np.where(change, np.arange(N), -1))]
    g = {"in": _grid(vals, np.ones(N, bool))}
    ref = partition_run(exe, g, 0, N // 32)
    for d in (jnp.asarray(change), jnp.ones(N, bool)):
        got = sp.sparse_run(exe, g, 0, N // 32, dirty={"in": d})
        _assert_same(ref, got, "explicit")
    mask = sp.segment_mask(exe, g, 0, N // 32,
                           dirty={"in": jnp.ones(N, bool)})
    assert np.asarray(mask).all()


def test_sparse_run_requires_sparse_compile():
    q = _trend(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=32, pallas=False)  # no sparse
    g = {"in": _grid(np.zeros(N, np.float32), np.ones(N, bool))}
    with pytest.raises(ValueError, match="sparse=True"):
        sp.sparse_run(exe, g, 0, N // 32)


def test_bucket_capacity_policy():
    assert sp.bucket_capacity(0, 16) == 1
    assert sp.bucket_capacity(1, 16) == 1
    assert sp.bucket_capacity(3, 16) == 4
    assert sp.bucket_capacity(9, 16) == 16
    assert sp.bucket_capacity(100, 16) == 16  # clipped to the segment count


def test_hypothesis_random_change_masks_never_alter_outputs():
    """Property: for *any* change mask (and any φ gaps), sparse ≡ dense."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    n = 128
    q = _trend(TStream.source("in", prec=1))
    exe = qc.compile_query(q.node, out_len=16, pallas=False, sparse=True)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.floats(0.0, 1.0),
           st.floats(0.0, 0.3))
    def prop(seed, rate, invalid_rate):
        rng = np.random.default_rng(seed)
        change = rng.random(n) < rate
        change[0] = True
        raw = np.floor(rng.random(n) * 100).astype(np.float32)
        vals = raw[np.maximum.accumulate(
            np.where(change, np.arange(n), -1))]
        valid = rng.random(n) >= invalid_rate
        g = {"in": _grid(vals, valid)}
        ref = partition_run(exe, g, 0, n // 16)
        got = sp.sparse_run(exe, g, 0, n // 16)
        _assert_same(ref, got, f"seed={seed}")

    prop()


# -- unit-window gather: contiguous slices ≡ the scalar (key, tick) index ----

def _scalar_windows(x, core, length, seg0, ids=None, per_row=None):
    """The unit-window gather as one scalar ``(key, tick)`` index pair per
    window element (``x[k_ids[:, None], tidx]``) — the reference the
    slice-shaped gather must reproduce bit for bit."""
    if ids is None:
        u = jnp.arange(x.shape[0] * per_row)
        ids = (u // per_row, u % per_row)
    k_ids, s_ids = ids
    tidx = (seg0 + s_ids)[:, None] * core + jnp.arange(length)[None, :]
    return x[k_ids[:, None], tidx]


# (rows, per_row segments of a shard, buffer segments, seg0, trailing dims):
# keyed (8 keys, whole keys per shard); a single-keyed shard at a segment
# offset of the replicated buffer; a value leaf with a trailing dim
WINDOW_LAYOUTS = {"keyed": (8, 4, 4, 0, ()),
                  "single_offset": (1, 4, 16, 8, ()),
                  "trailing": (8, 4, 4, 0, (3,))}
WINDOW_CASES = [(lay, cap) for lay, (rows, per_row, *_) in
                WINDOW_LAYOUTS.items()
                for cap in sp.capacity_ladder(rows * per_row)]


@pytest.mark.parametrize("layout,cap", WINDOW_CASES)
def test_unit_windows_match_scalar_index_gather(layout, cap):
    """Every bucket of the capacity ladder: the full-capacity bucket's
    static slices and the compacted buckets' window gather (ids as
    ``nonzero`` leaves them: sorted, padded with unit 0) equal the scalar
    index gather bit for bit, values and validity."""
    from repro.engine.runner import _unit_windows
    rows, per_row, n_buf, seg0, trail = WINDOW_LAYOUTS[layout]
    core, hl = 8, 13                       # a halo that is not whole blocks
    length, ticks = hl + core, hl + n_buf * core
    rng = np.random.default_rng(cap)
    xv = jnp.asarray(rng.standard_normal((rows, ticks) + trail),
                     jnp.float32)
    xm = jnp.asarray(rng.random((rows, ticks)) < 0.5)
    n_units = rows * per_row
    if cap == n_units:
        ids = None
    else:
        live = np.sort(rng.choice(n_units, max(cap - 1, 1), replace=False))
        nz = np.zeros(cap, np.int32)
        nz[:len(live)] = live
        ids = (jnp.asarray(nz // per_row), jnp.asarray(nz % per_row))
    for x in (xv, xm):
        got = _unit_windows(x, core, length, seg0, ids=ids, per_row=per_row)
        ref = _scalar_windows(x, core, length, seg0, ids=ids,
                              per_row=per_row)
        assert got.dtype == x.dtype
        assert got.shape == ref.shape == (cap, length) + x.shape[2:]
        assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("keyed", [True, False])
def test_runner_windows_bit_identical_to_scalar_index_gather(
        keyed, monkeypatch):
    """The fused sparse step with the slice-shaped window gather emits
    exactly what the same step built on the scalar index gather emits,
    over chunks whose dirty counts land in compacted buckets and in the
    full-capacity one."""
    from repro.engine import ExecPolicy, Runner
    from repro.engine import runner as runner_mod
    K = 8 if keyed else 1
    P, core, n_chunks = 4, 32, 6
    T = n_chunks * P * core
    rng = np.random.default_rng(21)
    vals = np.zeros((K, T), np.float32)
    valid = np.ones((K, T), bool)
    rates = np.repeat([0.3, 0.0, 0.004, 0.02, 0.002, 0.3], P * core)
    for k in range(K):
        change = rng.random(T) < rates
        change[0] = True
        raw = np.floor(rng.random(T) * 100).astype(np.float32)
        vals[k] = raw[np.maximum.accumulate(
            np.where(change, np.arange(T), -1))]
        valid[k, rng.integers(0, T - 8):][:8] = False

    def run():
        q = _trend(TStream.source("in", keyed=keyed))
        exe = qc.compile_query(q.node, out_len=core, pallas=False,
                               sparse=True)
        r = Runner(exe, ExecPolicy(body="sparse",
                                   keys="vmapped" if keyed else "single"),
                   n_keys=K if keyed else None, segs_per_chunk=P)
        outs, counts = [], []
        for c in range(n_chunks):
            sl = slice(c * P * core, (c + 1) * P * core)
            g = (keyed_grid(vals[:, sl], valid[:, sl], t0=sl.start)
                 if keyed else _grid(vals[0, sl], valid[0, sl], t0=sl.start))
            o = r.step({"in": g})
            outs.append((np.asarray(o.value), np.asarray(o.valid)))
            counts.append(int(np.asarray(r.last_seg_dirty).sum()))
        return outs, counts

    got, counts = run()
    monkeypatch.setattr(runner_mod, "_unit_windows", _scalar_windows)
    ref, ref_counts = run()
    assert counts == ref_counts
    U = K * P
    buckets = {sp.bucket_capacity(c, U) for c in counts}
    assert U in buckets and min(buckets) < U, (counts, buckets)
    for (gv, gm), (rv, rm) in zip(got, ref):
        assert np.array_equal(gm, rm)
        assert np.array_equal(gv, rv)
