"""Hillclimb driver: recompile one cell with config overrides and diff the
terms against the baseline JSON (hypothesis → change → measure loop).

Usage:
  PYTHONPATH=src python -m benchmarks.hillclimb dbrx-132b train_4k \
      --set seq_parallel=true --set n_micro... --tag iterA
Writes out/hillclimb/<arch>_<shape>_<tag>.json and prints the delta table.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--tag", default="iter")
    ap.add_argument("--baseline-dir", default="out/dryrun")
    ap.add_argument("--full", action="store_true",
                    help="include the unrolled cost lowering (slow)")
    ap.add_argument("--micro", type=int, default=None)
    args = ap.parse_args()

    out = f"out/hillclimb/{args.arch}_{args.shape}_{args.tag}.json"
    os.makedirs("out/hillclimb", exist_ok=True)
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", args.arch, "--shape", args.shape, "--mesh", "single",
           "--json", out]
    if not args.full:
        cmd.append("--skip-unrolled")
    if args.micro:
        cmd += ["--micro", str(args.micro)]
    for kv in args.set:
        cmd += ["--set", kv]
    # the dry run compiles for host devices only: keep it off any chip
    p = subprocess.run(cmd, capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if p.returncode != 0:
        print(p.stdout[-2000:], p.stderr[-2000:])
        sys.exit(1)

    with open(out) as f:
        new = json.load(f)
    base_path = os.path.join(args.baseline_dir,
                             f"{args.arch}_{args.shape}_single.json")
    base = json.load(open(base_path)) if os.path.exists(base_path) else {}

    def row(name, b, n, fmt="{:.3f}"):
        delta = ""
        if isinstance(b, (int, float)) and isinstance(n, (int, float)) and b:
            delta = f"  ({(n - b) / b * +100:+.1f}%)"
        print(f"{name:28s} {fmt.format(b) if b or b==0 else '-':>12s} -> "
              f"{fmt.format(n) if n or n==0 else '-':>12s}{delta}")

    bm, nm = base.get("memory", {}), new.get("memory", {})
    print(f"== {args.arch} × {args.shape} [{args.tag}] "
          f"overrides={new.get('overrides')}")
    row("arg GB", bm.get("argument_size_in_bytes", 0) / 1e9,
        nm.get("argument_size_in_bytes", 0) / 1e9)
    row("temp GB", bm.get("temp_size_in_bytes", 0) / 1e9,
        nm.get("temp_size_in_bytes", 0) / 1e9)
    row("collective_s (scanned)", base.get("collective_s_scanned", 0),
        new.get("collective_s_scanned", 0), "{:.4f}")
    br, nr = base.get("roofline") or {}, new.get("roofline") or {}
    if br and nr:
        for k in ("compute_s", "memory_s", "collective_s",
                  "roofline_fraction"):
            row(k, br.get(k, 0), nr.get(k, 0), "{:.4f}")


if __name__ == "__main__":
    main()
