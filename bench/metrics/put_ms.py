"""Median per served chunk of the program's ``serve.put`` span: the
host's enqueue of the chunk's commit to the device(s) ahead of the
step's dispatch (under mesh placement, to the runner's ingest sharding,
split over the chips by key).  ``None`` where the program opens no
such span in the window."""
import numpy as np
import program_trace


def read(ctx):
    data = program_trace.for_cell(ctx)
    if data is None:
        return None
    lo, hi = data["window"]
    puts = [e - s for n, s, e, _ in data["spans"]
            if n == "serve.put" and lo <= s < hi]
    return 1e-6 * float(np.median(puts)) if puts else None
