"""Device-busy time per served chunk over the traced window."""
from metrics import _shared


def read(ctx):
    return _shared.step_device_ms(ctx)
