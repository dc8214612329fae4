"""Device self time per served chunk of compaction: the count, the
bucket pick, the dirty units' ids and positions (``tilt.compact``) and
the scatter back (``tilt.scatter``)."""
from metrics import _phases


def read(ctx):
    return _phases.phase_ms(ctx, "tilt.compact", "tilt.scatter")
