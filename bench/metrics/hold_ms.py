"""Device self time per served chunk of the hold fill (ops under
``tilt.hold``)."""
from metrics import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "tilt.hold")
