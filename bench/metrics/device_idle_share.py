"""Share of the traced window with no operation on the device (the most
idle device), in %."""
from metrics import _shared


def read(ctx):
    return _shared.idle_share(ctx)
