"""Median wall per served call: the serve loop resumed for a chunk
(``serve()``) or the chunk put, dispatched and blocked on (``step()``),
until its result is complete."""
from metrics import _shared


def read(ctx):
    return _shared.median_ms(ctx.chunks, "call", "done")
