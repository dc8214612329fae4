"""Fixtures for the harness's own tests: the bench directory and the
program's sources on the path, and cells cut to a size the CPU runs in
interpret mode.  These tests never report a device number."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# every configuration at a size the Pallas interpreter serves in seconds
TINY = {"fraud": dict(keys=32, seg=64, segs_per_chunk=4,
                      app_args={"win": 1000}, sample_keys=32),
        "trend": dict(keys=16, seg=32, segs_per_chunk=4, sample_keys=8)}
# at the quiet mix's own rate a tiny cell sees about three flagged payments
# in ten chunks; five times the rate gives the sampled keys flagged payments
# to lose, with about half the units still clean (hold still does work)
TINY_MIX = {"quiet": {"activity": {"kind": "uniform", "p": 1e-3}}}
TINY_RATE = 400   # peak ticks per second of open-loop mixes on the CPU


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """``run`` with every configuration cut to :data:`TINY`, the CPU
    accepted as a device and its caches under a temporary directory."""
    import run
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    config, traffic, peaks = run.config, run.traffic, run.peaks

    def tiny_config(name):
        c = config(name)
        c.update(TINY[name])
        return c

    def tiny_traffic(name):
        t = traffic(name)
        t.update(TINY_MIX.get(name, {}))
        phases = t["pacing"].get("phases", [])
        top = max((p["ticks_per_s"] for p in phases), default=0)
        for p in phases:      # the same profile, its peak at TINY_RATE
            p["ticks_per_s"] *= TINY_RATE / top
        return t

    monkeypatch.setattr(run, "config", tiny_config)
    monkeypatch.setattr(run, "traffic", tiny_traffic)
    monkeypatch.setattr(run, "peaks", lambda: {
        **peaks(), "cpu": {"hbm_bytes_per_s": 1e10, "source": "test"}})
    monkeypatch.setattr(run, "OUT", str(tmp_path / "out"))
    return run
