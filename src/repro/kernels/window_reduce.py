"""Pallas TPU kernels for TiLT window reductions (DESIGN.md §2).

Two kernels cover every built-in reduction:

* :func:`prefix_scan` — multi-block inclusive prefix sum with a VMEM carry
  across the (sequential) grid.  Invertible reductions (sum/count/mean/
  stddev/moments) become ``P[t] - P[t-W]`` — Subtract-on-Evict vectorized
  over all ticks; the subtract itself is a cheap XLA slice, so the kernel is
  the bandwidth-bound scan.

* :func:`sliding_assoc` — exact W-tick sliding reduce for any associative
  combine (sum, max, min).  Each output block is reduced from itself and
  the block before it: log-step doubling builds ``f_s[t] = combine over
  [t-s+1, t]`` for ``s = 1, 2, 4, …`` and the output combines the disjoint
  power-of-two ranges of W's binary expansion, so every tick reads at most
  ``2·log2(W)`` rolled vectors.  Sums reduce at most W values per output
  (error bounded by the window's content, independent of stream length).

TPU mapping (checked by compiling for a described v5e chip in
tests/test_tpu_compile.py; CPU runs them with ``interpret=True``):

* Blocks are ``(C, B)``: C = channel rows on the sublane axis (the whole
  channel dim, so any C is a legal block), B on the lane axis a multiple of
  128 (:func:`_block_for`).  The wrappers pad T up to a multiple of B.
* Shifts along time are ``pltpu.roll`` lane rotations masked by lane
  position — no unaligned slices, no ``cumsum`` in the kernel body.
* The grid is 1-D; the prefix scan's carry lives in VMEM scratch, which
  persists across its sequential grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["prefix_scan", "sliding_assoc", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 1024  # lanes per grid step for the prefix scan
_LANES = 128


def _block_for(n: int) -> int:
    """Smallest multiple of the 128-lane tile covering ``n`` ticks."""
    return max(_LANES, -(-int(n) // _LANES) * _LANES)


# ---------------------------------------------------------------------------
# Kernel 1: multi-block prefix scan with carry
# ---------------------------------------------------------------------------

def _prefix_scan_kernel(x_ref, out_ref, carry_ref):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    p = x_ref[...].astype(jnp.float32)            # (C, B)
    lane = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    s = 1
    while s < p.shape[-1]:                         # Hillis-Steele scan
        p = p + jnp.where(lane >= s, pltpu.roll(p, s, 1), 0.0)
        s *= 2
    p = p + carry_ref[...]                         # carry (C, 1) broadcasts
    out_ref[...] = p
    carry_ref[...] = p[:, -1:]


def prefix_scan(x: jax.Array, block: int = DEFAULT_BLOCK,
                interpret: bool = True) -> jax.Array:
    """Inclusive f32 prefix sum along the last axis of ``x: (C, T)``.

    T is padded to a multiple of ``block`` (itself rounded up to the lane
    tile); the pad region is zeros so the carry is unaffected, and the
    wrapper slices the result back.
    """
    C, T = x.shape
    block = _block_for(block)
    Tp = -(-T // block) * block
    xp = jnp.pad(x, ((0, 0), (0, Tp - T)))

    out = pl.pallas_call(
        _prefix_scan_kernel,
        grid=(Tp // block,),
        in_specs=[pl.BlockSpec((C, block), lambda k: (0, k))],
        out_specs=pl.BlockSpec((C, block), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((C, Tp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, 1), jnp.float32)],
        interpret=interpret,
    )(xp)
    return out[:, :T]


# ---------------------------------------------------------------------------
# Kernel 2: sliding associative reduce by binary window decomposition
# ---------------------------------------------------------------------------

def _sliding_kernel(prev_ref, cur_ref, out_ref, *, window, combine):
    v = jnp.concatenate([prev_ref[...], cur_ref[...]], axis=-1)  # (C, 2B)
    B = cur_ref.shape[-1]
    f, acc, off, s = v, None, 0, 1
    # f = combine over [t-s+1, t]; acc = combine over [t-off+1, t].  Lanes
    # of the current block (>= B) never read past lane 0 (W - 1 <= B), so
    # the rotations' wrap-around only reaches discarded lanes.
    while s <= window:
        if window & s:
            part = f if off == 0 else pltpu.roll(f, off, 1)
            acc = part if acc is None else combine(acc, part)
            off += s
        s *= 2
        if s <= window:
            f = combine(f, pltpu.roll(f, s // 2, 1))
    out_ref[...] = acc[:, B:]


def sliding_assoc(x: jax.Array, window: int, combine, identity,
                  interpret: bool = True) -> jax.Array:
    """Sliding-window associative reduce along the last axis of ``x: (C, T)``.

    ``out[:, t] = combine over x[:, max(0, t-window+1) : t+1]``.

    The wrapper left-pads one block of ``identity`` (so block k-1 always
    exists and leading partial windows are exact) and right-pads T to a
    multiple of the block, which spans at least ``window - 1`` ticks.
    """
    C, T = x.shape
    W = int(window)
    if W <= 1:
        return x
    B = _block_for(W - 1)
    Tp = -(-T // B) * B
    xp = jnp.pad(x, ((0, 0), (B, Tp - T)), constant_values=identity)

    out = pl.pallas_call(
        functools.partial(_sliding_kernel, window=W, combine=combine),
        grid=(Tp // B,),
        in_specs=[
            pl.BlockSpec((C, B), lambda k: (0, k)),      # previous block
            pl.BlockSpec((C, B), lambda k: (0, k + 1)),  # current block
        ],
        out_specs=pl.BlockSpec((C, B), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((C, Tp), x.dtype),
        interpret=interpret,
    )(xp, xp)
    return out[:, :T]
