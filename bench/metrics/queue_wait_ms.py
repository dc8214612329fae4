"""95th percentile of the wait from a chunk's due time to the start of its
served call (cells with an open-loop schedule)."""
from metrics import _shared


def read(ctx):
    return _shared.p95_ms(ctx.chunks, "due", "call")
