#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload fraud.busy --chunks 22 \
        --seeds 101,102,... --control-seeds 201,202,203

For each of ``--seeds`` (one process, one service): a fresh stream of the
cell at its own size, ``--chunks`` chunks served through the window's
entry, compared with the float64 reference exactly as a run compares it —
the program's readings, whose largest is the lower reading of a limit.

For each of ``--control-seeds``: the control, the same reference computed
in the precision below the configuration's (``bfloat16`` for ``float32``:
every stream stored at that precision, sums still accumulated in
float64) and put in the program's place — its readings give the upper
reading.  One JSON line per reading; the control needs no chip, the
program does (without ``--seeds`` only the control runs).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import check
import generate
import run

LOWER = {"float32": "bfloat16"}   # the configuration's -> the control's


def control_readings(cell: str, seed: int, n_chunks: int, bm=None) -> dict:
    """The control in the program's place, at the cell's own size."""
    bm = bm if bm is not None else run.benchmark()
    wl = run.workload(bm, cell)
    cfg, mix = run.config(wl["config"]), run.traffic(wl["traffic"])
    span = cfg["seg"] * cfg["segs_per_chunk"]
    pool = generate.make_pool(mix, cfg["keys"], span, seed)
    value, valid = generate.stream_rows(pool, run.sample_keys(cfg, seed),
                                        n_chunks)
    ref_mod = run.reference(cfg["app"])
    ref = ref_mod.reference(value, valid, **cfg["app_args"])
    ctl = ref_mod.reference(value, valid, precision=LOWER[cfg["precision"]],
                            **cfg["app_args"])
    return check.compare(ctl["value"], ctl["valid"], ref,
                         cfg["limits"]["value_gap"])


def program_readings(svc, cell: str, seed: int, n_chunks: int,
                     bm=None) -> dict:
    """The program at the cell's own size: a fresh stream on ``svc``."""
    bm = bm if bm is not None else run.benchmark()
    wl = run.workload(bm, cell)
    cfg, mix = run.config(wl["config"]), run.traffic(wl["traffic"])
    span = cfg["seg"] * cfg["segs_per_chunk"]
    svc.runner.reset()
    pool = generate.make_pool(mix, cfg["keys"], span, seed)
    stream = run.Stream(svc, pool, run.sample_keys(cfg, seed), run._no_spans)
    # an open-loop mix has a schedule, a backlogged one has none
    paced = generate.schedule(mix["pacing"], span, 1.0) is not None
    run.serve_chunks(svc, stream, n_chunks, paced)
    return run.compare_stream(cfg, stream)


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chunks", type=int, required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    args = ap.parse_args(argv)
    bm = run.benchmark()
    for seed in args.control_seeds:
        r = control_readings(args.workload, seed, args.chunks, bm)
        print(json.dumps({"what": "control", "seed": seed, **r}), flush=True)
    if not args.seeds:
        return 0
    wl = run.workload(bm, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.OUT,
                                                           "jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    devices = run.require_devices(wl["chips"], run.peaks())
    svc = run.build(run.config(wl["config"]), devices)
    for seed in args.seeds:
        r = program_readings(svc, args.workload, seed, args.chunks, bm)
        print(json.dumps({"what": "program", "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
