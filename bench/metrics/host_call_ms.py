"""Median per served chunk of the host's time in the program's own
spans outside the wait for the device: its ``serve.put`` plus its
``serve.call`` less its ``serve.block``."""
import numpy as np
import program_trace


def read(ctx):
    data = program_trace.for_cell(ctx)
    if data is None:
        return None
    calls = program_trace.host_call_s(data)
    return 1e3 * float(np.median(calls)) if calls else None
