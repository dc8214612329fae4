"""BENCHMARK.json names only pieces that exist under bench/, and every
per-layer metric lists only cells that report what it moves."""
import os
import re

import generate
import run
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_piece_is_found_by_name():
    bm = run.benchmark()
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = run.config(c["name"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert hasattr(run.reference(cfg["app"]), "reference")
        assert set(cfg["limits"]) == {"value_gap", "flag_flips"}
    for w in bm["workloads"]:
        assert w["config"] in configs and NAME.match(w["name"])
        assert run.config(w["config"])["chips"] == w["chips"]
        mix = run.traffic(w["traffic"])
        for part in ("values", "activity", "pacing"):
            assert generate.kind(part, mix[part])
        assert len(w["why"]) <= 200
    for m in bm["per_layer"]:
        assert hasattr(run.reader(m["name"]), "read")


def test_split_metrics_share_one_reader():
    assert run.reader("call_ms.tput") is run.reader("call_ms.lat")
    assert run.reader("call_ms.tput").__file__.endswith("metrics/call_ms.py")


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    bm = run.benchmark()
    for w in bm["workloads"]:
        e2e = {m["name"] for m in run.metrics_of(bm, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(bm, w["name"], True)


def test_per_layer_cells_report_what_they_move():
    bm = run.benchmark()
    for m in bm["per_layer"]:
        for cell in m["workloads"]:
            e2e = {e["name"] for e in run.metrics_of(bm, cell, False)}
            assert m["moves"] in e2e, (m["name"], cell)


def test_bench_holds_no_stray_files():
    for dirpath, _, files in os.walk(BENCH):
        for f in files:
            assert re.match(r"^[A-Za-z0-9_./-]+$", f), f
