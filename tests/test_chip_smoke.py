"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size with the
Pallas kernels in interpret mode, and its refusal to run without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(keys=8, win=50, seg=32, segs=4, chunks=3, sample=4,
                        event_keys=4, event_chunks=2, lateness=16)


def test_chip_smoke_refuses_cpu():
    """No accelerator: non-zero exit and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU found" in p.stderr


def test_chip_smoke_deployment_is_fixed():
    """The command line selects chips and seed only; the served sizes are
    the deployment's."""
    assert chip_smoke.DEPLOYMENT == chip_smoke.Sizes(
        keys=4096, win=1000, seg=256, segs=8, chunks=8, sample=64,
        event_keys=64, event_chunks=3, lateness=64)
    assert chip_smoke.DEPLOYMENT.span == 2048
    with pytest.raises(SystemExit):
        chip_smoke.parse_args(["--keys", "8"])


def test_chip_smoke_one_chip_phases(monkeypatch):
    """Keyed deployment under both bodies plus the event path, each
    checked against the eventspe reference."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    out = chip_smoke.run_one(TINY, seed=0)
    assert set(out) == {"sparse", "dense", "events"}
    assert out["sparse"]["ref_flags"] > 0
    assert out["sparse"] == out["dense"]
    assert out["events"]["keys"] == 4


def test_chip_smoke_mesh_phases(monkeypatch):
    """The four-chip phases on the devices this process has: mesh
    placements and the multi-hop halo, bit for bit against local runs."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    sz = chip_smoke.Sizes(keys=8, win=150, seg=16, segs=4, chunks=3)
    devs = jax.devices()[:4]
    if 4 % len(devs):
        pytest.skip(f"{len(devs)} devices do not divide the segment count")
    out = chip_smoke.run_four(sz, 0, devs)
    assert all(r["identical"] for r in out.values())
    assert out["halo"]["hops"] >= 2
