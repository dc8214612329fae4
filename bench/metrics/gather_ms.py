"""Device self time per served chunk of the sparse step's unit-window
gather (ops under ``tilt.gather``)."""
from metrics import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "tilt.gather")
