"""Open loop: ticks fall due on a clock, whatever the system does.

``{"kind": "open_loop", "phases": [{"seconds": s, "ticks_per_s": r},
...]}``: the tick rate is ``r`` for ``s`` seconds, then the next phase's,
and the list repeats.  One phase is a fixed rate; two make bursts.  Every
key's tick ``i`` falls due once ``i + 1`` ticks have been offered, and a
chunk when its last tick has."""
import numpy as np


def schedule(spec: dict, span: int, seconds: float):
    """``(chunk due (n,), tick due (n, span))`` in seconds after the
    window opens, for the ``n`` chunks due within ``seconds``."""
    length = np.asarray([p["seconds"] for p in spec["phases"]], np.float64)
    rate = np.asarray([p["ticks_per_s"] for p in spec["phases"]],
                      np.float64)
    cycles = int(np.ceil(seconds / length.sum())) + 1
    t = np.concatenate([[0.0], np.cumsum(np.tile(length, cycles))])
    offered = np.concatenate([[0.0], np.cumsum(np.tile(length * rate,
                                                       cycles))])
    n = int(np.interp(seconds, t, offered) // span)
    ticks = np.interp(np.arange(1, n * span + 1, dtype=np.float64),
                      offered, t).reshape(n, span)
    return ticks[:, -1].copy(), ticks
