"""Fused change-detection kernel (paper §5's skip test off the critical
path).

Sparse execution resolves, per output segment, one bit: *did any input tick
in this segment's dilated lineage change?*  The staged implementation
(engine/runner phases, core/sparse one-shot) answers it in three jitted
passes — per-source tick diff, `ChangePlan` dilation via cumsum range
queries, per-segment reduction — materializing a full-length dirty mask
between them.  This kernel fuses all three into one ``pallas_call`` per
channel matrix, the per-matrix segment flags ORed together:

* Every source grid is flattened into per-dtype channel matrices ``(C, T)``
  (:func:`grid_mats`): value leaves become rows, the validity mask is cast
  in as one more row, so "any leaf or validity changed" is one vectorized
  ``!=`` across rows.
* The dilated lineage of segment ``k`` is the *affine* input range
  ``[a0 + k·step, a0 + k·step + width)``
  (:func:`repro.core.plan.seg_range_affine`) — a fixed-width window
  sliding a fixed stride per segment.  The 1-D grid maps a group of
  ``G`` consecutive segments (``G·step`` a multiple of the 128-lane tile,
  so any stride gives lane-aligned blocks) onto consecutive input blocks
  of the same padded matrix (the multi-``in_specs`` idiom), diffs
  adjacent ticks in registers and reduces to one flag per segment,
  written as a ``(G, 1)`` column — the tick-level mask never exists in
  memory.
* Out-of-range and tick-0 pairs are masked by position (NaN-safe: padding
  content is never compared), matching the reference convention that tick
  0 never changed — carried cross-chunk flags are the caller's to OR in.

Semantics of record: :func:`repro.kernels.ref.seg_dirty_fused_ref` (the
dispatcher's jnp fallback on non-TPU backends, and what CI asserts
bit-identity against in interpret mode).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops, ref

__all__ = ["grid_mats", "seg_dirty"]

_LANES = 128        # TPU lane tile: every block is a multiple of it
_MIN_BLOCK = 1024   # grow small-stride groups to at least this many ticks


def grid_mats(value, valid) -> list:
    """Flatten one source grid's ``(value, valid)`` into channel matrices
    ``(C, T)`` for :func:`seg_dirty` — one matrix per value dtype (rows
    can only be compared vectorized within a dtype), validity cast in as a
    row of the first.  Time axis 0 in, time axis last out; bool leaves are
    widened to int32 (exact).  Traceable (vmap-safe over a leading key
    axis)."""
    groups: dict = {}
    for leaf in jax.tree_util.tree_leaves(value):
        x = leaf.astype(jnp.int32) if leaf.dtype == jnp.bool_ else leaf
        rows = x.reshape(x.shape[0], -1).T if x.ndim > 1 else x[None, :]
        groups.setdefault(str(rows.dtype), []).append(rows)
    if not groups:
        return [valid[None, :].astype(jnp.int32)]
    first = next(iter(groups))
    groups[first].append(valid[None, :].astype(groups[first][0].dtype))
    return [rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
            for rows in groups.values()]


def _lower(a0: int, step: int, width: int, T: int, n_segs: int):
    """Static block geometry for one matrix.  Segments go ``G`` per grid
    step, ``G`` a power of two with ``G·step`` a multiple of the 128-lane
    tile, so every block is lane-aligned whatever the stride: block ``B =
    G·step`` ticks, group ``g`` reads ticks ``[a0 - 1 + g·B, ...)`` (the
    extra leading tick is the diff partner) as ``NB`` consecutive blocks of
    a left-padded matrix, starting at block ``g + m``.  Returns ``(G, B, NB,
    m, pad_left, pad_to, n_groups)``."""
    step = max(int(step), 1)
    G = _LANES // math.gcd(step, _LANES)
    while G * step < _MIN_BLOCK and G < n_segs:
        G *= 2
    B = G * step
    shift = a0 - 1
    pad_left = (-shift) % B
    m = (pad_left + shift) // B
    if m < 0:
        pad_left += -m * B
        m = 0
    n_groups = -(-n_segs // G)
    NB = -(-(B - step + width + 1) // B)
    need = (n_groups + m + NB - 1) * B
    pad_to = -(-max(need, pad_left + T) // B) * B
    return G, B, NB, m, pad_left, pad_to, n_groups


def _kernel(*refs, a0, step, width, T, G, B):
    """One grid step = ``G`` consecutive segments.  Walks the group's
    window a lane tile at a time: diff each tick against its predecessor
    (``pltpu.roll`` by one lane, the tile's lane 0 taking the previous
    tile's last tick), OR over rows, mask to in-range pairs, and OR the
    tick flags into every segment row whose lineage band covers them —
    a ``(G, 128)`` accumulator reduced across lanes once at the end."""
    out_ref, x_refs = refs[-1], refs[:-1]
    g = pl.program_id(0)
    C = x_refs[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (G, _LANES), 0)
    lo = 1 + row * step                 # segment rows' first pair position
    acc = jnp.zeros((G, _LANES), jnp.int32)
    carry = jnp.zeros((C, _LANES), x_refs[0].dtype)
    for j, x_ref in enumerate(x_refs):

        def tile(c, state, x_ref=x_ref, j=j):
            acc, carry = state
            off = pl.multiple_of(c * _LANES, _LANES)
            x = x_ref[:, pl.ds(off, _LANES)]
            rolled = pltpu.roll(x, 1, 1)
            prev = jnp.where(lane == 0, carry, rolled)
            d = jnp.max((x != prev).astype(jnp.int32), axis=0, keepdims=True)
            r = j * B + off + lane      # position in the group's window
            t = a0 - 1 + g * B + r      # global tick index
            d = jnp.where((r >= 1) & (t >= 1) & (t <= T - 1), d, 0)
            hit = (r >= lo) & (r < lo + width)
            return jnp.maximum(acc, jnp.where(hit, d, 0)), rolled

        acc, carry = jax.lax.fori_loop(0, B // _LANES, tile, (acc, carry))
    out_ref[...] = jnp.max(acc, axis=1, keepdims=True)


def _seg_dirty_one(x, geom, n_segs: int, interpret: bool):
    a0, step, width = geom
    C, T = x.shape
    G, B, NB, m, pad_left, pad_to, n_groups = _lower(a0, step, width, T,
                                                     n_segs)
    xp = jnp.pad(x, ((0, 0), (pad_left, pad_to - pad_left - T)))
    in_specs = [pl.BlockSpec((C, B), functools.partial(
                    lambda g, b: (0, g + b), b=m + j)) for j in range(NB)]
    out = pl.pallas_call(
        functools.partial(_kernel, a0=a0, step=step, width=width, T=T,
                          G=G, B=B),
        grid=(n_groups,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, G, 1), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, G, 1), jnp.int32),
        interpret=interpret,
        name="seg_dirty",   # the custom call's name in a device trace
    )(*([xp] * NB))
    return out.reshape(n_groups * G)[:n_segs] > 0


def _seg_dirty_pallas(mats, geoms, n_segs: int, interpret: bool):
    seg = jnp.zeros((n_segs,), bool)
    for x, geom in zip(mats, geoms):
        if geom[2] > 0:
            seg = seg | _seg_dirty_one(x, geom, n_segs, interpret)
    return seg


def seg_dirty(mats, geoms, n_segs: int, pallas: bool | None = None
              ) -> jax.Array:
    """Per-segment dirty flags ``(n_segs,) bool``: segment ``k`` is dirty
    iff any tick in ``[a0 + k·step, a0 + k·step + width)`` of any matrix
    differs from its predecessor tick (tick 0 and out-of-range ticks never
    count — carried flags are the caller's to OR in).

    ``mats``/``geoms`` are parallel lists — (C, T) channel matrices
    (:func:`grid_mats`) and their static ``(a0, step, width)`` lineage
    triples (:func:`repro.core.plan.seg_range_affine`); a source with
    several dtype matrices repeats its triple.  Dispatch follows
    kernels/ops: the Pallas kernel on TPU (or under
    ``REPRO_PALLAS_INTERPRET=1``), the jnp oracle
    :func:`repro.kernels.ref.seg_dirty_fused_ref` elsewhere.
    """
    if pallas is None:
        pallas = ops.use_pallas()
    if pallas:
        return _seg_dirty_pallas(mats, geoms, n_segs, ops._interpret())
    return ref.seg_dirty_fused_ref(mats, geoms, n_segs)
