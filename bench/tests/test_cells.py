"""A tiny end-to-end rehearsal of every cell on the CPU, kernels in
interpret mode, through the harness's own run; and the refusal to run
without a TPU.  Nothing here is a device number."""
import json
import os
import subprocess
import sys

import jax
import pytest

import run
from conftest import BENCH

CELLS = [w["name"] for w in run.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearsal(tiny, cell, traced):
    chips = tiny.config(run.workload(run.benchmark(), cell)["config"])["chips"]
    out = tiny.run_cell(cell, 2**32 + 17, 1.0, traced, jax.devices()[:chips])
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    names = {m["name"] for m in run.metrics_of(run.benchmark(), cell,
                                               traced)}
    assert set(out["metrics"]) <= names
    assert list(out)[-1] == "checks"
    if not traced:
        assert set(out["metrics"]) == names
        assert out["metrics"]["setup_s"]["value"] > 0


def test_refuses_cpu():
    """No accelerator: a non-zero exit and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "no TPU found" in p.stderr


def test_refuses_unknown_device_kind(monkeypatch):
    dev = type("D", (), {"platform": "tpu", "device_kind": "TPU v99"})()
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.require_devices(1, run.peaks())


def test_refuses_too_few_chips(monkeypatch):
    dev = type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    with pytest.raises(SystemExit, match="needs 4 TPU chips"):
        run.require_devices(4, run.peaks())


def test_result_line_is_json(tiny, capsys, monkeypatch):
    monkeypatch.setattr(run, "require_devices",
                        lambda chips, table: jax.devices()[:1])
    assert run.main(["--workload", "fraud.busy", "--seed", "5",
                     "--seconds", "0.5", "--trace", "0"]) == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert cap.err.strip().splitlines()[-1].startswith("check flag_flips=")
