"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py) and
writes a machine-readable ``BENCH_<section>.json`` per executed section
(rows + parsed derived columns + config) so the perf trajectory is
trackable across PRs; slow CI uploads the JSONs as artifacts.
Scale with REPRO_BENCH_EVENTS (default 2M events — the paper uses 160M on
a 32-core machine; this container is 1 core).

Runs either as a module (``python -m benchmarks.run figsparse``) or as a
plain script (``python benchmarks/run.py figsparse``).
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):
    # plain-script invocation: make the repo root (for ``benchmarks``) and
    # src/ (for ``repro``) importable before any package import
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (os.path.join(_ROOT, "src"), _ROOT):
        if _p not in sys.path:
            sys.path.insert(0, _p)


# bounded dry-run seed grid for the roofline section when out/dryrun is
# empty: three representative (arch × shape) cells, single mesh, one per
# subprocess (dryrun forces 512 host devices at import, so it must not run
# in-process).  Default cells skip the unrolled cost lowering (~10 s each:
# compile proof, memory/fits, scanned collective bytes); set
# REPRO_BENCH_ROOFLINE_COST=1 to add the full cost/roofline columns
# (~4 min per cell on this 1-core container).
_ROOFLINE_CELLS = (("qwen3-1.7b", "train_4k"),
                   ("gemma2-2b", "prefill_32k"),
                   ("granite-moe-1b-a400m", "train_4k"))


def _roofline(roofline_table, out_dir: str = "out/dryrun") -> None:
    import glob
    import subprocess
    if not glob.glob(os.path.join(out_dir, "*.json")):
        os.makedirs(out_dir, exist_ok=True)
        cost = os.environ.get("REPRO_BENCH_ROOFLINE_COST") == "1"
        for arch, shape in _ROOFLINE_CELLS:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", "single",
                   "--json",
                   os.path.join(out_dir, f"{arch}_{shape}_single.json")]
            if not cost:
                cmd += ["--skip-unrolled"]
            try:
                # the dry run compiles for host devices only: keep the
                # child off any accelerator this process may hold
                subprocess.run(cmd, timeout=2400, check=False,
                               capture_output=True,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            except subprocess.TimeoutExpired:
                pass  # run_cell records its own failure JSON when it can
    roofline_table.run(out_dir)


def main() -> None:
    n = int(os.environ.get("REPRO_BENCH_EVENTS", 2_000_000))
    only = sys.argv[1] if len(sys.argv) > 1 else None

    # the halo-depth sweep shards time across devices; force a multi-device
    # host platform BEFORE jax is imported (flag is read at backend init).
    # Only when that section alone runs — the rest keep the default config.
    ndev = os.environ.get("REPRO_BENCH_DEVICES")
    if ndev is None and only == "fighalo":
        ndev = "8"
    if ndev and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(ndev)}").strip()

    # warm XLA compiles across benchmark runs (JAX_COMPILATION_CACHE_DIR or
    # the checkout's fixed out/jax_cache; opt out with
    # REPRO_BENCH_JAX_CACHE=0)
    if os.environ.get("REPRO_BENCH_JAX_CACHE") != "0":
        from repro.serve import enable_jax_compilation_cache
        enable_jax_compilation_cache()

    from benchmarks import (common, fig7_throughput, fig8_keyed_scaling,
                            fig8_ysb_scaling, fig9_latency, fig10_fusion,
                            fig_halo_depth, fig_latency,
                            fig_multiquery_sharing, fig_ooo, fig_policy,
                            fig_sparse, metrics_smoke, roofline_table)

    sections = {
        "fig7": lambda: fig7_throughput.run(n),
        "fig8": lambda: fig8_ysb_scaling.run(n),
        "fig8k": lambda: fig8_keyed_scaling.run(min(n, 1_000_000)),
        "fig9": lambda: fig9_latency.run(min(n, 1_000_000)),
        "fig10": lambda: fig10_fusion.run(n),
        "figmq": lambda: fig_multiquery_sharing.run(min(n, 1_000_000)),
        "fighalo": lambda: fig_halo_depth.run(min(n, 1_000_000)),
        "figsparse": lambda: fig_sparse.run(n),
        "figpolicy": lambda: fig_policy.run(min(n, 1_000_000)),
        "figooo": lambda: fig_ooo.run(min(n, 1_000_000)),
        "figlat": lambda: fig_latency.run(min(n, 1_000_000)),
        "metricssmoke": lambda: metrics_smoke.run(min(n, 1_000_000)),
        "roofline": lambda: _roofline(roofline_table),
    }
    for name, fn in sections.items():
        if only and only != name:
            continue
        print(f"## section {name}")
        common.begin_section(name, config={"events": n})
        fn()
        path = common.end_section()
        if path:
            print(f"# wrote {path}")


if __name__ == "__main__":
    main()
