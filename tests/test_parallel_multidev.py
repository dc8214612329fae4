"""Multi-device tests via subprocess (the main pytest process must keep the
default 1-device CPU config; these spawn fresh interpreters with
``--xla_force_host_platform_device_count=8``)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

TIMEOUT = 420


def _run(script: str) -> str:
    code = textwrap.dedent(script)
    # JAX_PLATFORMS must survive into the stripped env: without it jax
    # probes for a TPU backend and hangs until TIMEOUT on isolated hosts.
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=TIMEOUT,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            "JAX_PLATFORMS":
                                os.environ.get("JAX_PLATFORMS", "cpu")})
    assert p.returncode == 0, f"stdout={p.stdout}\nstderr={p.stderr[-3000:]}"
    return p.stdout


def test_shard_map_halo_exchange_matches_host_loop():
    """The ppermute halo exchange (paper Fig. 6 as SPMD) must reproduce the
    single-device result exactly, including across-shard windows."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import compile as qc
        from repro.core.frontend import TStream
        from repro.core.parallel import partition_run, shard_map_run
        from repro.core.stream import SnapshotGrid
        from repro.launch.mesh import make_local_mesh

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(0)
        N = 1024
        vals = rng.normal(size=N).astype(np.float32)
        valid = rng.random(N) > 0.2
        g = {"in": SnapshotGrid(value=jnp.asarray(vals),
                                valid=jnp.asarray(valid), t0=0, prec=1)}

        s = TStream.source("in", prec=1)
        q = (s.window(20).mean()
              .join(s.window(50).mean(), lambda a, b: a - b)
              .where(lambda d: d > 0))

        full = partition_run(
            qc.compile_query(q.node, out_len=N, pallas=False), g, 0, 1)

        mesh = make_local_mesh(n_data=8)
        exe = qc.compile_query(q.node, out_len=N // 8, pallas=False)
        shard = shard_map_run(exe, g, mesh, axis="data")

        m1, m2 = np.asarray(full.valid), np.asarray(shard.valid)
        assert np.array_equal(m1, m2), (m1.sum(), m2.sum())
        v1, v2 = np.asarray(full.value), np.asarray(shard.value)
        np.testing.assert_allclose(v1[m1], v2[m1], rtol=1e-5, atol=1e-5)
        print("HALO_OK")
    """)
    assert "HALO_OK" in out


def test_shard_map_multi_hop_bit_identical_to_partition_run():
    """Deep-lookback configs the seed rejected (halo > per-shard core) must
    run through the multi-hop ppermute chain and match the host loop
    *bit-for-bit* on integer-valued data (same partitioning ⇒ identical
    float association; see the float caveat in repro/multiquery).

    Covers 2-hop, 3-hop and the acceptance config (window 500 over 8
    shards of 128 core ticks ⇒ 4-hop left halo), non-zero origins, and the
    right-halo chain via multi-hop lookahead (shift(-d)) configs.
    """
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import compile as qc
        from repro.core.frontend import TStream
        from repro.core.parallel import (partition_run, shard_map_run,
                                         check_single_hop_halo)
        from repro.core.stream import SnapshotGrid
        from repro.launch.mesh import make_local_mesh

        assert len(jax.devices()) == 8
        mesh = make_local_mesh(n_data=8)

        # lookback (left chain): (window, total ticks, hops, origin),
        # core = N // 8
        configs = [(100, 512, 2, 0),     # core 64  -> 2 hops
                   (100, 320, 3, 0),     # core 40  -> 3 hops
                   (500, 1024, 4, 0),    # core 128 -> 4 hops (acceptance)
                   (500, 1024, 4, 4096)] # ... at a non-zero origin
        # lookahead (right chain has its own trim direction, permutation
        # and segment order): shift(-d) needs ceil(d/core) right hops
        la_configs = [(150, 512, 3, 0),  # core 64 -> 3 right hops
                      (70, 256, 3, 128)] # core 32 -> 3 right hops, t0!=0
        for kind, W, N, hops, t0 in (
                [("lb",) + c for c in configs]
                + [("la",) + c for c in la_configs]):
            rng = np.random.default_rng(W + N)
            vals = rng.integers(0, 100, N).astype(np.float32)
            valid = rng.random(N) > 0.2
            g = {"in": SnapshotGrid(value=jnp.asarray(vals),
                                    valid=jnp.asarray(valid),
                                    t0=t0, prec=1)}
            s = TStream.source("in", prec=1)
            q = s.window(W).sum() if kind == "lb" else s.shift(-W)
            exe = qc.compile_query(q.node, out_len=N // 8, pallas=False)
            rep = check_single_hop_halo(exe.input_specs, exe.out_prec, 8)
            got = (rep["in"].left_hops if kind == "lb"
                   else rep["in"].right_hops)
            assert got == hops, (kind, W, N, rep)

            ref = partition_run(exe, g, t0, 8)
            shard = shard_map_run(exe, g, mesh, axis="data")
            assert shard.t0 == t0, (shard.t0, t0)
            m1, m2 = np.asarray(ref.valid), np.asarray(shard.valid)
            assert np.array_equal(m1, m2), (kind, W, N, m1.sum(), m2.sum())
            v1, v2 = np.asarray(ref.value), np.asarray(shard.value)
            assert np.array_equal(v1[m1], v2[m1]), (kind, W, N)
        print("MULTIHOP_OK")
    """)
    assert "MULTIHOP_OK" in out


def test_sparse_run_matches_shard_map_run():
    """Change-compressed execution vs SPMD time-sharded execution: dirty
    spans crossing shard boundaries (a multi-hop-deep window) must agree
    bit-for-bit on integer-valued data with both partition_run and
    shard_map_run over the same partitioning."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import compile as qc
        from repro.core.frontend import TStream
        from repro.core.parallel import partition_run, shard_map_run
        from repro.core.sparse import segment_mask, sparse_run
        from repro.core.stream import SnapshotGrid

        assert len(jax.devices()) == 8
        N, n_shards = 512, 8
        # piecewise-constant integers; the change at tick 300 sits mid
        # shard 4 and its 100-tick lookback span crosses shard boundaries
        vals = np.full(N, 11.0, np.float32)
        vals[140:] = 4.0
        vals[300:] = 27.0
        valid = np.ones(N, bool)
        valid[200:230] = False
        g = {"in": SnapshotGrid(value=jnp.asarray(vals),
                                valid=jnp.asarray(valid), t0=0, prec=1)}
        s = TStream.source("in", prec=1)
        q = s.window(100).sum()   # halo 100 > core 64: 2-hop exchange
        exe = qc.compile_query(q.node, out_len=N // n_shards,
                               pallas=False, sparse=True)

        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()), ("data",))
        ref = partition_run(exe, g, 0, n_shards)
        shard = shard_map_run(exe, g, mesh, axis="data")
        got = sparse_run(exe, g, 0, n_shards)
        mask = np.asarray(segment_mask(exe, g, 0, n_shards))
        assert 1 < mask.sum() < n_shards, mask.astype(int)  # real compaction
        for other, name in ((shard, "shard"), (got, "sparse")):
            m1, m2 = np.asarray(ref.valid), np.asarray(other.valid)
            assert np.array_equal(m1, m2), (name, m1.sum(), m2.sum())
            v1, v2 = np.asarray(ref.value), np.asarray(other.value)
            assert np.array_equal(v1[m1], v2[m1]), name
        print("SPARSE_SHARD_OK")
    """)
    assert "SPARSE_SHARD_OK" in out


def test_shard_union_run_deep_windows_match_session():
    """Time-sharded union execution: merged multi-query halo contracts
    deeper than the per-shard span (4-hop) must match the chunked
    MultiQuerySession bit-for-bit on integer-valued data."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.frontend import TStream
        from repro.core.stream import SnapshotGrid
        from repro.launch.mesh import make_local_mesh
        from repro.multiquery import MultiQuerySession, shard_union_run

        N, n_shards = 512, 8
        span = N // n_shards                  # 64 per shard
        rng = np.random.default_rng(9)
        vals = rng.integers(0, 50, N).astype(np.float32)
        valid = rng.random(N) > 0.2
        g = {"in": SnapshotGrid(value=jnp.asarray(vals),
                                valid=jnp.asarray(valid), t0=0, prec=1)}
        s = TStream.source("in", prec=1)
        queries = {"shallow": s.window(16).mean(),   # 1 hop
                   "deep": s.window(200).sum()}      # merged halo: 4 hops

        mesh = make_local_mesh(n_data=n_shards)
        out = shard_union_run(queries, span, g, mesh, axis="data",
                              pallas=False)

        sess = MultiQuerySession(span, pallas=False)
        for name, q in queries.items():
            sess.attach(name, q)
        ref = sess.run(g, n_shards)
        for name in queries:
            m1 = np.asarray(ref[name].valid)
            m2 = np.asarray(out[name].valid)
            assert np.array_equal(m1, m2), name
            v1 = np.asarray(ref[name].value)
            v2 = np.asarray(out[name].value)
            assert np.array_equal(v1[m1], v2[m1]), name
        print("UNION_SHARD_OK")
    """)
    assert "UNION_SHARD_OK" in out


def test_policy_sparse_mesh_multidev_bit_identical():
    """Acceptance: ExecPolicy(body=sparse, placement=mesh) on an 8-device
    mesh — both keys='single' (segments shard, per-shard compaction over
    local segments) and keys='vmapped' (keys shard, per-shard compaction
    over local keys; the composition KeyedEngine(sparse=True) used to
    reject) — is bit-identical to the dense local reference on
    integer-valued data, and the compaction buckets stay per-shard sized.
    """
    out = _run("""
        import os, warnings
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        warnings.simplefilter("ignore", DeprecationWarning)
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import compile as qc
        from repro.core.frontend import TStream
        from repro.core.stream import SnapshotGrid
        from repro.engine import (ExecPolicy, KeyedEngine, Runner,
                                  keyed_grid, mesh_placement)

        assert len(jax.devices()) == 8
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))

        def pw(shape, rate, seed):
            rng = np.random.default_rng(seed)
            ch = rng.random(shape) < rate
            ch[..., 0] = True
            raw = np.floor(rng.random(shape) * 100).astype(np.float32)
            idx = np.maximum.accumulate(
                np.where(ch, np.arange(shape[-1]), -1), axis=-1)
            vals = (np.take_along_axis(raw, idx, axis=-1)
                    if len(shape) > 1 else raw[idx])
            return vals, np.ones(shape, bool)

        def trend(s):
            return (s.window(16).mean()
                    .join(s.window(32).mean(), lambda a, b: a - b)
                    .where(lambda d: d > 0))

        def same(a, b, ctx):
            m1, m2 = np.asarray(a.valid), np.asarray(b.valid)
            assert np.array_equal(m1, m2), (ctx, m1.sum(), m2.sum())
            assert np.array_equal(np.asarray(a.value)[m1],
                                  np.asarray(b.value)[m1]), ctx

        # -- keys='single': segments shard over the mesh ------------------
        N = 512
        vals, valid = pw((N,), 0.02, seed=1)
        g = {"in": SnapshotGrid(value=jnp.asarray(vals),
                                valid=jnp.asarray(valid), t0=0, prec=1)}
        q = trend(TStream.source("in", prec=1))
        exe_d = qc.compile_query(q.node, out_len=32, pallas=False)
        exe_s = qc.compile_query(q.node, out_len=32, pallas=False,
                                 sparse=True)
        ref = Runner(exe_d, ExecPolicy()).run(g, N // 32)
        got = Runner(exe_s, ExecPolicy(body="sparse",
                                       placement=mesh_placement(mesh)),
                     segs_per_chunk=8).run(g, N // 256)
        same(ref, got, "single")
        caps = sorted(k[-1] for k in exe_s._runner_step_cache
                      if isinstance(k, tuple) and k[0] == "compute")
        assert caps and caps[0] <= 1, caps  # <=1 dirty segment per shard

        # -- keys='vmapped': keys shard, sparse x mesh composition --------
        K, T, P = 32, 256, 4
        kv, km = pw((K, T), 0.0, seed=2)       # idle keys...
        av, am = pw((4, T), 0.2, seed=3)
        kv[::8], km[::8] = av, am              # ...except every 8th
        gk = {"in": keyed_grid(kv, km)}
        qk = trend(TStream.source("in", keyed=True))
        exe_kd = qc.compile_query(qk.node, out_len=T // P, pallas=False)
        exe_ks = qc.compile_query(qk.node, out_len=T // P, pallas=False,
                                  sparse=True)
        refk = KeyedEngine(exe_kd, n_keys=K).run(gk, P)
        gotk = KeyedEngine(exe_ks, n_keys=K, mesh=mesh, sparse=True
                           ).run(gk, P)
        same(refk, gotk, "keyed-engine")
        rp = Runner(exe_ks, ExecPolicy(body="sparse", keys="vmapped",
                                       placement=mesh_placement(mesh)),
                    n_keys=K)
        same(refk, rp.run(gk, P), "keyed-runner")
        caps = sorted(k[-1] for k in exe_ks._runner_step_cache
                      if isinstance(k, tuple) and k[0] == "compute")
        # 4 active keys over 8 shards: per-shard buckets stay tiny (the
        # forced-dense first step uses the full local capacity K/8 = 4)
        assert caps and caps[0] <= 2, caps
        print("POLICY_MESH_OK")
    """)
    assert "POLICY_MESH_OK" in out


def test_served_mesh_placements_bit_identical_on_4_devices():
    """``chip_smoke.py --chips 4``'s phases on 4 host devices: served
    runners under mesh placement (keys sharded; one stream's segments
    sharded) and the multi-hop one-shot halo, each bit for bit against
    one device.  Pins the AOT steps agreeing on the sharded dirty flags
    (the metrics accumulator was once lowered for single-device flags)."""
    out = _run("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, ".")
        import jax
        import chip_smoke as cs
        sz = cs.Sizes(keys=8, win=50, seg=32, segs=4, chunks=3)
        res = cs.run_four(sz, 0, jax.devices()[:4])
        assert all(r["identical"] for r in res.values()), res
        assert res["halo"]["hops"] == 2, res
        print("MESH_OK")
    """)
    assert "MESH_OK" in out


def test_sparse_union_session_mesh_multidev():
    """Acceptance: a sparse union session (merged ChangePlan, keyed × mesh)
    is bit-identical to its dense solo counterparts on integer data."""
    out = _run("""
        import os, warnings
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        warnings.simplefilter("ignore", DeprecationWarning)
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import compile as qc
        from repro.core.frontend import TStream
        from repro.engine import KeyedEngine, keyed_grid
        from repro.multiquery import MultiQuerySession

        assert len(jax.devices()) == 8
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        K, T, SPAN = 16, 256, 64
        rng = np.random.default_rng(7)
        ch = rng.random((K, T)) < 0.03
        ch[:, 0] = True
        raw = np.floor(rng.random((K, T)) * 100).astype(np.float32)
        idx = np.maximum.accumulate(
            np.where(ch, np.arange(T), -1), axis=-1)
        vals = np.take_along_axis(raw, idx, axis=-1)
        valid = np.ones((K, T), bool)
        g = {"in": keyed_grid(vals, valid)}

        s = TStream.source("in", prec=1, keyed=True)
        queries = {"trend": (s.window(16).mean()
                             .join(s.window(32).mean(), lambda a, b: a - b)
                             .where(lambda d: d > 0)),
                   "bands": s.window(24).max().join(s, lambda h, x: h - x)}

        sess = MultiQuerySession(SPAN, n_keys=K, mesh=mesh, pallas=False,
                                 sparse=True)
        for name, q in queries.items():
            sess.attach(name, q)
        outs = sess.run(g, T // SPAN)
        for name, q in queries.items():
            exe = qc.compile_query(q.node, out_len=SPAN, pallas=False)
            ref = KeyedEngine(exe, n_keys=K).run(g, T // SPAN)
            m1, m2 = np.asarray(ref.valid), np.asarray(outs[name].valid)
            assert np.array_equal(m1, m2), (name, m1.sum(), m2.sum())
            assert np.array_equal(np.asarray(ref.value)[m1],
                                  np.asarray(outs[name].value)[m1]), name
        print("SPARSE_UNION_MESH_OK")
    """)
    assert "SPARSE_UNION_MESH_OK" in out


def test_dryrun_cell_small_mesh():
    """End-to-end dry-run machinery on an 8-device mesh (2 data × 4 model):
    lower+compile a smoke-size train step with the production sharding
    rules, verifying the sharding.py → pjit pipeline."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs.base import registry, Shape
        from repro.models.model import build_model
        from repro.models import shardctx
        from repro.launch import sharding as SH
        from repro.train.train_step import make_train_step
        from repro.train.optimizer import AdamWConfig, init_opt_state

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        shardctx.set_mesh_axes(mesh.axis_names)
        import dataclasses
        cfg = registry()["qwen3-1.7b"][1]
        cfg = dataclasses.replace(cfg, n_layers=4, d_ff=128, d_model=64,
                                  n_heads=4, n_kv_heads=4)
        model = build_model(cfg)
        params, axes = model.init(jax.random.PRNGKey(0))
        psh = SH.param_shardings(axes, cfg, mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, psh)
        opt = init_opt_state(params)
        step = make_train_step(model, AdamWConfig())
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.zeros((8, 32), jnp.int32)}
        with mesh:
            p2, o2, m = jax.jit(step)(params, opt, batch)
        assert jnp.isfinite(m["loss"])
        print("DRYRUN_SMALL_OK", float(m["loss"]))
    """)
    assert "DRYRUN_SMALL_OK" in out


def test_elastic_checkpoint_reshard():
    """Save on an 8-device mesh, restore onto a 4-device mesh (elastic
    downscale after simulated node loss)."""
    out = _run("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ck

        mesh8 = jax.make_mesh((8,), ("data",))
        sh8 = NamedSharding(mesh8, P("data"))
        tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sh8),
                "b": jax.device_put(jnp.ones(8), sh8),
                "opt": {"m": jax.device_put(jnp.zeros((8, 8)), sh8)}}
        d = tempfile.mkdtemp()
        ck.save(d, 3, tree, extra={"pipeline_pos": 1234})

        # restore on a smaller mesh (first 4 devices)
        mesh4 = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
        sh4 = {"w": NamedSharding(mesh4, P("data")),
               "b": NamedSharding(mesh4, P("data")),
               "opt": {"m": NamedSharding(mesh4, P("data"))}}
        restored, manifest = ck.restore(d, shardings=sh4)
        assert manifest["extra"]["pipeline_pos"] == 1234
        assert restored["w"].sharding.mesh.devices.size == 4
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
