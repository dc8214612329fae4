"""Share of work units (key x segment) that computed in the window:
the runner's ``runner.dirty_units`` over ``runner.units``, in %."""


def read(ctx):
    units = ctx.counters.get("runner.units", 0)
    if not units or "runner.dirty_units" not in ctx.counters:
        return None
    return 100.0 * ctx.counters["runner.dirty_units"] / units
