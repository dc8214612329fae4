"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle
across a shape/dtype/window sweep, plus the fast jnp block fallback."""
import os

os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import window_reduce as wr

SHAPES = [(64, 1, 8), (257, 2, 16), (533, 3, 37), (1024, 4, 128),
          (100, 1, 100), (96, 2, 256)]  # window > T included
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("T,C,W", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_prefix_scan_kernel(T, C, W, dtype):
    rng = np.random.default_rng(T + C)
    x = jnp.asarray(rng.normal(size=(C, T)), dtype)
    out = wr.prefix_scan(x, block=64, interpret=True)
    want = np.cumsum(np.asarray(x, np.float32), axis=-1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("T,C,W", SHAPES)
def test_vanherk_kernel_max_min(T, C, W):
    rng = np.random.default_rng(T * 7 + W)
    x = jnp.asarray(rng.normal(size=(C, T)).astype(np.float32))
    valid = jnp.asarray(rng.random(T) > 0.3)
    for op, comb, ident in (("max", jnp.maximum, -jnp.inf),
                            ("min", jnp.minimum, jnp.inf)):
        v, a = ops.sliding_assoc(x, valid, W, op, pallas=True)
        xm = jnp.where(valid[None], x, ident)
        vr, ar = ref.sliding_assoc_ref(xm, valid, W, comb, ident)
        np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-6)
        assert np.array_equal(np.asarray(a), np.asarray(ar)), op


@pytest.mark.parametrize("T,C,W", SHAPES)
@pytest.mark.parametrize("algo", ["block", "soe"])
@pytest.mark.parametrize("pallas", [True, False])
def test_sliding_sum(T, C, W, algo, pallas):
    rng = np.random.default_rng(T + W)
    x = jnp.asarray(rng.normal(size=(C, T)).astype(np.float32))
    valid = jnp.asarray(rng.random(T) > 0.2)
    s, n = ops.sliding_sum(x, valid, W, pallas=pallas, algo=algo)
    sr, nr = ref.sliding_sum_ref(x, valid, W)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(n), np.asarray(nr), atol=0.5)


@pytest.mark.parametrize("T,C,W", SHAPES + [(1300, 2, 1000), (400, 3, 50),
                                             (300, 2, 20)])
@pytest.mark.parametrize("algo", ["block", "soe"])
def test_sliding_sum_kernel_exact_on_integers(T, C, W, algo):
    """On integer-valued data every float sum is exact, so the kernels
    (their own summation order) must equal the jnp path bit for bit."""
    rng = np.random.default_rng(T * 3 + W)
    x = jnp.asarray(rng.integers(-50, 50, size=(C, T)).astype(np.float32))
    valid = jnp.asarray(rng.random(T) > 0.3)
    s_k, n_k = ops.sliding_sum(x, valid, W, pallas=True, algo=algo)
    s_r, n_r = ops.sliding_sum(x, valid, W, pallas=False, algo=algo)
    assert np.array_equal(np.asarray(s_k), np.asarray(s_r))
    assert np.array_equal(np.asarray(n_k), np.asarray(n_r))


@pytest.mark.parametrize("W", [8, 20, 50, 127, 128, 129, 1000])
@pytest.mark.parametrize("op", ["max", "min"])
def test_sliding_assoc_kernel_matches_block_ref(W, op):
    """Max/min are exact in any order: kernel == jnp block path, at window
    sizes on and off the 128-lane tile."""
    rng = np.random.default_rng(W)
    x = jnp.asarray(rng.normal(size=(2, 1500)).astype(np.float32))
    valid = jnp.asarray(rng.random(1500) > 0.3)
    v_k, a_k = ops.sliding_assoc(x, valid, W, op, pallas=True)
    v_r, a_r = ops.sliding_assoc(x, valid, W, op, pallas=False)
    assert np.array_equal(np.asarray(v_k), np.asarray(v_r))
    assert np.array_equal(np.asarray(a_k), np.asarray(a_r))


def test_block_beats_soe_numerics():
    """The beyond-paper block algorithm must bound error by window content;
    SoE error grows with stream length (DESIGN.md §2)."""
    T, W = 200_000, 64
    rng = np.random.default_rng(0)
    xs = (rng.normal(1000.0, 1.0, T)).astype(np.float32)  # large DC offset
    x = jnp.asarray(xs)[None, :]
    valid = jnp.ones((T,), bool)
    want = ref.sliding_sum_ref(x, valid, W)[0]
    # float64 oracle
    c = np.concatenate([[0], np.cumsum(xs.astype(np.float64))])
    exact = c[W:] - c[:-W]
    s_block, _ = ops.sliding_sum(x, valid, W, pallas=False, algo="block")
    s_soe, _ = ops.sliding_sum(x, valid, W, pallas=False, algo="soe")
    err_block = np.abs(np.asarray(s_block)[0, W:] - exact[:-1 or None][:len(exact)])
    err_block = np.abs(np.asarray(s_block)[0, W - 1:] - exact).max()
    err_soe = np.abs(np.asarray(s_soe)[0, W - 1:] - exact).max()
    assert err_block < 0.5, err_block
    assert err_soe > err_block * 10, (err_soe, err_block)


# (T, C, n_segs, a0, step, width): negative window starts, width > T,
# step > width (strided outputs) and single-tick widths all included
SEG_DIRTY_GEOMS = [
    (256, 1, 8, 0, 32, 32),
    (256, 3, 8, -31, 32, 64),      # window runs off the left edge
    (200, 2, 4, 7, 48, 17),        # step > width: gaps between lineages
    (64, 1, 4, -5, 16, 128),       # width > T: every segment sees the end
    (512, 4, 16, 1, 32, 33),
    (96, 2, 12, -8, 8, 1),         # single-pair windows
]


@pytest.mark.parametrize("T,C,n_segs,a0,step,width", SEG_DIRTY_GEOMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_seg_dirty_kernel_matches_ref(T, C, n_segs, a0, step, width, dtype):
    """The fused change-detection kernel (interpret mode on CPU) must be
    bit-identical to the jnp oracle on piecewise-constant channel matrices
    across lineage geometries, including out-of-range and tick-0 pairs
    (which never count, by convention)."""
    from repro.kernels import sparse_compact
    rng = np.random.default_rng(T * 31 + n_segs)
    # piecewise-constant rows (~5% change rate) so flags actually vary
    change = rng.random((C, T)) < 0.05
    raw = rng.integers(0, 50, size=(C, T))
    idx = np.maximum.accumulate(np.where(change, np.arange(T)[None, :], -1),
                                axis=1)
    x = jnp.asarray(raw[np.arange(C)[:, None], np.clip(idx, 0, None)], dtype)
    geoms = [(a0, step, width)]
    got = sparse_compact.seg_dirty([x], geoms, n_segs, pallas=True)
    want = ref.seg_dirty_fused_ref([x], geoms, n_segs)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_seg_dirty_kernel_multiple_matrices_and_nan():
    """Several matrices of different dtypes OR into one flag set; NaN
    payloads compare unequal to themselves and are always dirty —
    conservative in kernel and oracle alike (padding must NOT leak in)."""
    from repro.kernels import sparse_compact
    T, n_segs = 128, 4
    a = np.zeros((1, T), np.float32)
    a[0, 60] = np.nan                      # NaN tick: always dirty
    b = np.zeros((2, T), np.int32)
    b[1, 100:] = 7                         # int change in the last segment
    geoms = [(0, 32, 32), (0, 32, 32)]
    mats = [jnp.asarray(a), jnp.asarray(b)]
    got = sparse_compact.seg_dirty(mats, geoms, n_segs, pallas=True)
    want = ref.seg_dirty_fused_ref(mats, geoms, n_segs)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # the NaN at tick 60 dirties segment 1 only (pairs (59,60) and (60,61)
    # both land in ticks 32..63); the int change dirties segment 3
    assert list(np.asarray(want)) == [False, True, False, True]


def test_vanherk_block_ref_matches_reduce_window():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 300)).astype(np.float32))
    for W in (8, 33, 128):
        got = ref.sliding_assoc_block_ref(x, W, jnp.maximum, -jnp.inf)
        want = jnp.stack([ref.sliding_reduce_window_ref(
            x[c], W, -jnp.inf, jax_max) for c in range(2)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def jax_max(a, b):
    import jax.numpy as j
    return j.maximum(a, b)
