"""Arithmetic the per-layer readers share (each reader stays one file;
loaded by ``bench/run.py``, with ``bench/`` on the path)."""
import numpy as np


def median_ms(chunks, start: str, end: str):
    d = [c[end] - c[start] for c in chunks if start in c and end in c]
    return 1e3 * float(np.median(d)) if d else None


def p95_ms(chunks, start: str, end: str):
    d = [c[end] - c[start] for c in chunks if start in c and end in c]
    return 1e3 * float(np.percentile(d, 95)) if d else None


def _traced_ops(ctx) -> bool:
    return ctx.trace is not None and any(
        d["ops"] for d in ctx.trace["devices"].values())


def step_device_ms(ctx):
    if not _traced_ops(ctx) or not ctx.chunks:
        return None
    return 1e3 * ctx.trace["busy_s"] / len(ctx.chunks)


def idle_share(ctx):
    """The most idle device's idle share of the window, in %."""
    if not _traced_ops(ctx):
        return None
    return 100.0 * max(d["idle_share"] for d in ctx.trace["devices"].values())
