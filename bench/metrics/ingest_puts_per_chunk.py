"""Chunk leaves the runner had to put on its ingest sharding itself, per
chunk over the window: ``runner.ingest_puts`` over ``runner.chunks`` (0
when the serve loop committed every chunk ahead of the step).  ``None``
where the program keeps no such counter."""


def read(ctx):
    chunks = ctx.counters.get("runner.chunks", 0)
    if not chunks or "runner.ingest_puts" not in ctx.counters:
        return None
    return ctx.counters["runner.ingest_puts"] / chunks
