"""Median per served chunk of the time from the end of its step program
on the device (the ``jit_tilt_*`` module launched inside its
``serve.call`` span) to the end of its ``serve.block`` span: how late the
host sees a finished chunk."""
import numpy as np
import program_trace


def read(ctx):
    data = program_trace.for_cell(ctx)
    if data is None:
        return None
    lags = program_trace.completion_lags(data)
    return 1e3 * float(np.median(lags)) if lags else None
