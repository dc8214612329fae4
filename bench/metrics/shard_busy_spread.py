"""Spread of the devices' busy time over the traced window: (max - min)
over the mean of each device's busy seconds, in %.  Near 0 when the
shards carry equal work; large when one chip sets the pace."""
from metrics import _shared


def read(ctx):
    if not _shared._traced_ops(ctx) or len(ctx.trace["devices"]) < 2:
        return None
    busy = [d["busy_s"] for d in ctx.trace["devices"].values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
