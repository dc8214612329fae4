"""The served chunk path under keyed mesh placement on 4 host devices
(subprocesses: the main pytest process keeps the default 1-device CPU
config).

* The serve loop's double buffer commits every chunk to the runner's
  ingest sharding before the step that reads it is dispatched, so the
  runner puts nothing itself (``runner.ingest_puts`` stays 0), the steady
  chunks run under ``jax.transfer_guard("disallow")``, and outputs and
  carried tails stay split by key over all 4 devices — bit for bit what
  local placement serves on integer amounts.
* On float amounts the mesh-served stream agrees with the float64 fraud
  reference within the ``fraud_x4`` configuration's own limits.
* A second process loads every four-device step from the persisted
  executable cache.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

TIMEOUT = 420

PRELUDE = """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "bench")
    import json
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.stream import SnapshotGrid
    from repro.data.apps import make_keyed_app
    from repro.engine import ExecPolicy
    from repro.serve import build_service

    K, WIN, SEG, SEGS, CHUNKS = 8, 100, 32, 4, 6
    SPAN = SEG * SEGS
    app = make_keyed_app("fraud", win=WIN)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), ("data",))

    def chunks(value, valid):
        for c in range(CHUNKS):
            sl = np.s_[:, c * SPAN:(c + 1) * SPAN]
            yield {"in": SnapshotGrid(value=value[sl], valid=valid[sl],
                                      t0=c * SPAN, prec=1)}

    def service(placement):
        pol = ExecPolicy(body="sparse", keys="vmapped", placement=placement)
        return build_service(app.query, out_len=SEG, policy=pol, n_keys=K,
                             segs_per_chunk=SEGS)

    def counter(svc, name):
        return svc.runner.metrics.snapshot()["counters"][name]["value"]
"""


def _run(script: str) -> str:
    code = textwrap.dedent(PRELUDE) + textwrap.dedent(script)
    # JAX_PLATFORMS must survive into the stripped env: without it jax
    # probes for a TPU backend and hangs until TIMEOUT on isolated hosts.
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    if "HOME" in os.environ:
        env["HOME"] = os.environ["HOME"]
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=TIMEOUT, env=env)
    assert p.returncode == 0, f"stdout={p.stdout}\nstderr={p.stderr[-3000:]}"
    return p.stdout


def test_mesh_double_buffer_feeds_the_step_placed_and_bit_identical():
    out = _run("""
        rng = np.random.default_rng(3)
        valid = rng.random((K, SPAN * CHUNKS)) < 0.3
        value = np.where(valid, rng.integers(1, 120, valid.shape),
                         0).astype(np.float32)

        svc = service(mesh)
        runner = svc.runner
        want = runner.ingest_sharding()
        assert len(want.device_set) == 4 and not want.is_fully_replicated
        handed = []
        step = runner.step

        def spy(chunks):
            handed.append([x.sharding for x in (chunks["in"].value,
                                                chunks["in"].valid)])
            return step(chunks)

        runner.step = spy
        puts0 = counter(svc, "runner.ingest_puts")
        served = svc.serve(chunks(value, valid))
        last = next(served)
        got = [jax.device_get((last.value, last.valid))]
        with jax.transfer_guard("disallow"):       # the steady chunks
            for last in served:
                got.append(jax.device_get((last.value, last.valid)))
        assert len(handed) == CHUNKS
        assert all(s.is_equivalent_to(want, 2) for h in handed for s in h)
        assert counter(svc, "runner.ingest_puts") == puts0
        assert counter(svc, "serve.put_bytes") == value.nbytes + valid.nbytes
        for x in (last.value, last.valid, *runner._tails["in"]):
            sh = x.sharding
            assert len(sh.device_set) == 4 and not sh.is_fully_replicated
            assert sh.shard_shape(x.shape)[0] * 4 == K

        # the runner's own put of a chunk the caller left on the host
        runner.step = step
        runner.reset()
        before = counter(svc, "runner.ingest_puts")
        runner.step(next(chunks(value, valid)))
        assert counter(svc, "runner.ingest_puts") == before + 2

        local = service("local")
        ref = [jax.device_get((o.value, o.valid))
               for o in local.serve(chunks(value, valid))]
        for (gv, gm), (lv, lm) in zip(got, ref):
            assert np.array_equal(gm, lm)
            assert np.array_equal(gv[gm], lv[lm])
        print("MESH_PUT_OK", int(sum(m.sum() for _, m in ref)))
    """)
    assert "MESH_PUT_OK" in out
    assert "MESH_PUT_OK 0" not in out     # some flags to compare


def test_mesh_served_fraud_within_fraud_x4_limits():
    out = _run("""
        import check
        import generate
        from refs import fraud as ref_fraud

        with open("bench/configs/fraud_x4.json") as f:
            cfg = json.load(f)
        with open("bench/traffic/busy.json") as f:
            mix = json.load(f)
        mix["pool_chunks"] = CHUNKS
        pool = generate.make_pool(mix, K, SPAN, 2**32 + 5)
        value = np.concatenate(list(pool.value), axis=1)
        valid = np.concatenate(list(pool.valid), axis=1)
        svc = service(mesh)
        outs = [jax.device_get((o.value, o.valid))
                for o in svc.serve(chunks(value, valid))]
        gv = np.concatenate([v for v, _ in outs], axis=1)
        gm = np.concatenate([m for _, m in outs], axis=1)
        ref = ref_fraud.reference(value, valid, win=WIN)
        r = check.compare(gv, gm, ref, cfg["limits"]["value_gap"])
        ok, shown = check.verdict(r, cfg["limits"])
        assert ok and r["flag_flips"] == 0, shown
        assert r["compared"] > 0 and r["flags"] > 0, r
        print("MESH_REF_OK", json.dumps(r))
    """)
    assert "MESH_REF_OK" in out


def test_mesh_warm_start_loads_every_step(tmp_path):
    """A second process over the same caches loads every staged step of
    the keyed mesh service, the four-device executables included, and
    serves what the first process served, bit for bit."""
    script = """
        rng = np.random.default_rng(11)
        valid = rng.random((K, SPAN * CHUNKS)) < 0.3
        value = np.where(valid, rng.integers(1, 120, valid.shape),
                         0).astype(np.float32)
        pol = ExecPolicy(body="sparse", keys="vmapped", placement=mesh)
        svc = build_service(app.query, out_len=SEG, policy=pol, n_keys=K,
                            segs_per_chunk=SEGS, cache_dir=%r)
        outs = [jax.device_get((o.value, o.valid))
                for o in svc.serve(chunks(value, valid))]
        np.savez(%r, *[x for o in outs for x in o])
        print("REPORT", svc.plan_source, json.dumps(svc.aot_report),
              len(svc.runner.metrics.tracer.compiles()))
    """
    cache = str(tmp_path / "svc")
    reports, saved = [], []
    for i in range(2):
        path = str(tmp_path / f"out{i}.npz")
        out = _run(script % (cache, path))
        (line,) = [ln for ln in out.splitlines() if ln.startswith("REPORT")]
        _, how, rest = line.split(" ", 2)
        report, compiles = rest.rsplit(" ", 1)
        reports.append((how, json.loads(report), int(compiles)))
        saved.append(np.load(path))
    (cold, cold_aot, n_cold), (warm, warm_aot, n_warm) = reports
    assert cold == "cold" and set(cold_aot.values()) == {"compiled"}
    assert warm == "warm" and n_warm == 0
    assert warm_aot == {k: "loaded" for k in cold_aot}
    assert {"sparse_fused(first)", "sparse_fused(steady)",
            "obs_accum"} == set(warm_aot)
    for name in saved[0].files:
        assert np.array_equal(saved[0][name], saved[1][name]), name
