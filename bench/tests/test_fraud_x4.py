"""A tiny rehearsal of the four-chip cell ``fraud_x4.busy`` on 4 virtual
CPU devices, through the harness's own run (a subprocess: this process
keeps one CPU device), and its three readers of its own.  Nothing here
is a device number."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import types

import pytest

import run
from conftest import BENCH, ROOT

CELL = "fraud_x4.busy"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
QUIET = os.path.join(DATA, "fraud_quiet_small.xplane.pb")
TREND = os.path.join(DATA, "trend_paced_small.xplane.pb")

# the cell cut to a size the Pallas interpreter serves in seconds, keys
# still split 4 ways
SCRIPT = """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax
    import run
    config, peaks = run.config, run.peaks

    def tiny(name):
        c = config(name)
        c.update(keys=32, seg=64, segs_per_chunk=4, sample_keys=32)
        return c

    run.config = tiny
    run.peaks = lambda: {{**peaks(),
                         "cpu": {{"hbm_bytes_per_s": 1e10, "source": "test"}}}}
    run.OUT = {out!r}
    out = run.run_cell({cell!r}, 2**32 + 17, 1.0, {traced!r},
                       jax.devices()[:4])
    print(json.dumps(out))
"""


def _run_cell(tmp_path, traced: bool) -> dict:
    code = textwrap.dedent(SCRIPT).format(
        bench=BENCH, src=os.path.join(ROOT, "src"), out=str(tmp_path / "out"),
        cell=CELL, traced=traced)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=ROOT, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traced", [False, True])
def test_fraud_x4_rehearsal_on_four_devices(tmp_path, traced):
    bm = run.benchmark()
    assert run.workload(bm, CELL)["chips"] == 4
    out = _run_cell(tmp_path, traced)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    listed = {m["name"]: m for m in run.metrics_of(bm, CELL, traced)}
    got = out["metrics"]
    assert set(got) <= set(listed)
    if not traced:
        assert set(got) == {"events_per_s", "setup_s"}
        return
    # a CPU trace holds no TPU plane: what a device trace gives is absent,
    # everything the host and the program give is there
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert host <= set(got), host - set(got)
    assert got["ingest_puts_per_chunk"]["value"] == 0
    assert got["put_ms.tput"]["value"] > 0
    assert got["dirty_unit_share"]["value"] == 100


def _ctx(cell, n_chunks=3, devices=None):
    trace = None if devices is None else {"devices": devices}
    return types.SimpleNamespace(cell={"name": cell}, chunks=[{}] * n_chunks,
                                 counters={}, trace=trace)


def test_put_ms_reads_the_put_span(monkeypatch, tmp_path):
    """The recorded fraud.quiet trace (``fraud_quiet_small.xplane.pb``)
    has ``serve.put`` spans; the recorded trend.paced trace, of a program
    that opened no spans, reads ``None``."""
    read = run.reader("put_ms.tput").read
    for name, src in (("fraud.quiet", QUIET), ("trend.paced", TREND)):
        cell = tmp_path / "trace" / name
        cell.mkdir(parents=True)
        shutil.copy(src, cell / "t.xplane.pb")
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    got = read(_ctx("fraud.quiet"))
    assert got is not None and 0 < got < 10
    assert read(_ctx("trend.paced")) is None
    assert read(_ctx("no.trace")) is None


def test_ingest_puts_per_chunk_reads_the_counters():
    read = run.reader("ingest_puts_per_chunk").read
    ctx = _ctx(CELL)
    assert read(ctx) is None
    ctx.counters = {"runner.chunks": 10}
    assert read(ctx) is None           # a program without the counter
    ctx.counters = {"runner.chunks": 10, "runner.ingest_puts": 20}
    assert read(ctx) == 2.0


def test_shard_busy_spread_of_four_devices():
    read = run.reader("shard_busy_spread.tput").read
    dev = {i: {"busy_s": b, "ops": 5} for i, b in
           enumerate([1.0, 1.2, 0.8, 1.0])}
    assert read(_ctx(CELL, devices=dev)) == pytest.approx(40.0)
    assert read(_ctx(CELL)) is None
    assert read(_ctx(CELL, devices={0: dev[0]})) is None
    idle = {i: {"busy_s": 0.0, "ops": 0} for i in range(4)}
    assert read(_ctx(CELL, devices=idle)) is None
