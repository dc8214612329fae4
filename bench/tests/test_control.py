"""The control: the plain reference computed one precision below the
configuration's and put in the program's place must come out not
correct, while the program itself, on the same stream, is correct."""
import jax
import numpy as np
import pytest

import calibrate
import check
import run

CELLS = [w["name"] for w in run.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(tiny, cell):
    wl = run.workload(run.benchmark(), cell)
    cfg = run.config(wl["config"])
    ctl = calibrate.control_readings(cell, 7, 100)
    assert ctl["compared"] > 0
    ok, shown = check.verdict(ctl, cfg["limits"])
    assert not ok, shown
    svc = run.build(cfg, jax.devices()[:1])
    prog = calibrate.program_readings(svc, cell, 7, 100)
    ok, shown = check.verdict(prog, cfg["limits"])
    assert ok, shown
    assert prog["compared"] > 0


def test_reference_against_itself_reads_zero():
    r = run.reference("trend")
    v = np.cumsum(np.ones((2, 300)), axis=1)
    m = np.ones((2, 300), bool)
    ref = r.reference(v, m, short=20, long=50)
    got = check.compare(ref["value"], ref["valid"], ref, 1e-3)
    assert got["value_gap"] == 0 and got["flag_flips"] == 0
