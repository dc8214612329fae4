"""Roofline share (HBM bandwidth) of the window kernels: the Pallas
custom calls staged under ``ops.sliding_sum`` / ``ops.sliding_assoc``
(HLO names ``*jit_sliding_sum*``, ``*jit_sliding_assoc*``).  Bytes from
the call shapes (``bench/roofline.py``), time from the device trace."""
import re

import roofline

NAME = re.compile(r"jit_sliding_(sum|assoc)")


def read(ctx):
    if ctx.trace is None:
        return None
    return roofline.share(ctx.trace, NAME, ctx.peak["hbm_bytes_per_s"])
