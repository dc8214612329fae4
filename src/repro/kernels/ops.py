"""Jit'd dispatch wrappers around the Pallas window-reduction kernels.

``ops`` is the only kernel entry point the rest of the package uses; it
chooses between the Pallas kernel and the pure-jnp reference by backend
(:func:`use_pallas`), unless the caller passes ``pallas`` explicitly:

* On TPU: the Pallas kernels, compiled (never interpreted).
* Elsewhere: the jnp reference, which lowers to the same XLA ops the
  kernels implement by hand — unless ``REPRO_PALLAS_INTERPRET=1`` (the
  tests set it), which runs the kernels in interpret mode: far too slow
  for benchmark-scale runs, but the same kernel bodies the chip compiles.
* Windows below ``_SMALL_W`` take no kernel for max/min (a direct
  shift-combine in jnp) and the prefix-scan path for sums: at that size
  the window kernel's block overhead exceeds the O(W) work.

:func:`repro.kernels.sparse_compact.seg_dirty` dispatches the same way.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import window_reduce as _wr

__all__ = ["sliding_sum", "sliding_assoc", "use_pallas"]

_SMALL_W = 8


def use_pallas() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("window", "pallas", "algo"))
def sliding_sum(x: jax.Array, valid: jax.Array, window: int,
                pallas: bool | None = None,
                algo: str = "block") -> tuple[jax.Array, jax.Array]:
    """Masked sliding-window sums for (C, T) channels + (T,) valid count.

    Two algorithms, same O(1)-per-tick asymptotics:

    * ``algo='soe'``   — the paper-faithful Subtract-on-Evict: global prefix
      scan (Pallas kernel on TPU), then ``P[t] - P[t-W]`` as an XLA slice.
      FP32 CAVEAT: the cancellation error grows like ``eps·t·mean`` with
      stream position — unusable beyond ~10⁶ ticks of O(100) values.
    * ``algo='block'`` — beyond-paper numerical fix (DESIGN.md): every
      output sums only its own window — block-local prefix/suffix sums with
      block size = W (the Van Herk structure with ``combine=+``) in jnp, the
      binary window decomposition of :func:`window_reduce.sliding_assoc` in
      the kernel.  Error is bounded by the *window* content
      (``eps·W·mean``), independent of stream length.  Default.
    """
    pallas = use_pallas() if pallas is None else pallas
    C, T = x.shape
    xm = jnp.where(valid[None, :], x, 0).astype(jnp.float32)
    stacked = jnp.concatenate([xm, valid[None, :].astype(jnp.float32)], axis=0)
    if algo == "block" and window >= _SMALL_W:
        if pallas:
            s = _wr.sliding_assoc(stacked, window, jnp.add, 0.0,
                                  interpret=_interpret())
        else:
            s = _ref.sliding_assoc_block_ref(
                stacked, window, jnp.add, 0.0,
                scan_fn=lambda a, rev: (
                    jnp.flip(jnp.cumsum(jnp.flip(a, 2), axis=2), 2)
                    if rev else jnp.cumsum(a, axis=2)))
        return s[:C], s[C]
    if pallas:
        p = _wr.prefix_scan(stacked, interpret=_interpret())
    else:
        p = _ref.prefix_sum_ref(stacked)
    pw = jnp.pad(p, ((0, 0), (window, 0)))[:, :T]
    s = p - pw
    return s[:C], s[C]


@functools.partial(jax.jit, static_argnames=("window", "op", "pallas"))
def sliding_assoc(x: jax.Array, valid: jax.Array, window: int, op: str,
                  pallas: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Masked sliding-window max/min for (C, T) channels.

    Returns (values (C, T), any_valid (T,) bool).  Validity rides along as
    an extra channel (sliding any == sliding max of the mask).
    """
    pallas = use_pallas() if pallas is None else pallas
    combine = jnp.maximum if op in ("max", "absmax") else jnp.minimum
    identity = -jnp.inf if op in ("max", "absmax") else jnp.inf
    C, T = x.shape
    xm = jnp.where(valid[None, :], x, identity).astype(jnp.float32)
    vch = valid[None, :].astype(jnp.float32)
    if op == "min":
        # any-valid via max even when the payload combine is min
        stacked = jnp.concatenate([xm, -vch], axis=0)
    else:
        stacked = jnp.concatenate([xm, vch], axis=0)
    if window < _SMALL_W:
        out, anyv = _ref.sliding_assoc_ref(xm, valid, window, combine,
                                           identity)
        return out, anyv
    if not pallas:
        out = _ref.sliding_assoc_block_ref(stacked, window, combine,
                                           identity)
        vals = out[:C]
        anyv = (out[C] < -0.5) if op == "min" else (out[C] > 0.5)
        return vals, anyv
    out = _wr.sliding_assoc(stacked, window, combine, identity,
                            interpret=_interpret())
    vals = out[:C]
    # mask channel: sliding-OR via max(v) for max-ops, min(-v) for min-ops
    anyv = (out[C] < -0.5) if op == "min" else (out[C] > 0.5)
    return vals, anyv
