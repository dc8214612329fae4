"""Bytes a kernel call must move, from the shapes in its HLO text: each
distinct operand read once and the result written once.  A kernel's
roofline share is the least time those bytes take at the chip's HBM
bandwidth (``bench/peaks.json``) over the kernel's device time.  The
window kernels do a few vector adds per element and no matrix work, so
the bandwidth bound is the larger one."""
from __future__ import annotations

import re

__all__ = ["call_bytes", "share"]

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OPERAND = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]\{[^}]*\} (%[\w.\-]+)")


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES[dtype]


def call_bytes(text: str) -> int:
    """``%k = f32[..]{..} custom-call(f32[..]{..} %a, f32[..]{..} %a), ...``
    -> bytes of the result plus each distinct operand."""
    lhs, rhs = text.split(" custom-call(", 1)
    result = lhs.split(" = ", 1)[1]
    out = sum(_nbytes(t, d) for t, d in _ARRAY.findall(result))
    args = rhs.split("), ", 1)[0]
    seen = {name: _nbytes(t, d) for t, d, name in _OPERAND.findall(args)}
    return out + sum(seen.values())


def share(trace: dict, name: re.Pattern, bytes_per_s: float):
    """Roofline share in % of the custom calls whose HLO name matches
    ``name`` (``None`` when the trace holds none)."""
    t = b = 0.0
    for op, sec in trace["op_s"].items():
        text = trace["op_text"][op]
        if name.search(op) and " custom-call(" in text and sec > 0:
            t += sec
            b += trace["op_calls"][op] * call_bytes(text)
    if t <= 0:
        return None
    return 100.0 * (b / bytes_per_s) / t
