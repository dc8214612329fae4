"""Device programs the runner launches per chunk over the window:
``runner.dispatches`` over ``runner.chunks``."""


def read(ctx):
    chunks = ctx.counters.get("runner.chunks", 0)
    if not chunks or "runner.dispatches" not in ctx.counters:
        return None
    return ctx.counters["runner.dispatches"] / chunks
