"""Backlogged: the next chunk is always ready, so there is no schedule and
the window is served as fast as the system goes.  ``{"kind":
"backlogged"}``."""


def schedule(spec: dict, span: int, seconds: float):
    return None
