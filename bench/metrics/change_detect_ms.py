"""Device self time per served chunk of change detection (ops under
``tilt.change_detect``: the grid matrices, the ``seg_dirty`` kernel,
the tick-0 and adjacent diffs and the carried tails)."""
from metrics import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "tilt.change_detect")
