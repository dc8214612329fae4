"""The traffic generator: determined by the seed, fixed work per seed,
idle ticks that never change, and the open-loop schedule."""
import json
import os

import numpy as np
import pytest

import generate

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_pool_large_seed():
    seed = 2**31 + 12345
    for name in ("busy", "quiet", "paced"):
        a = generate.make_pool(mix(name), 16, 256, seed)
        b = generate.make_pool(mix(name), 16, 256, seed)
        np.testing.assert_array_equal(a.value, b.value)
        np.testing.assert_array_equal(a.valid, b.valid)
        c = generate.make_pool(mix(name), 16, 256, seed + 1)
        assert not np.array_equal(a.value, c.value)


def test_every_seed_offers_the_same_work():
    for name, p in (("busy", 0.3), ("quiet", 2e-4)):
        counts = {tuple(generate.make_pool(mix(name), 64, 512, s).valid
                        .sum(axis=(1, 2))) for s in range(5)}
        assert len(counts) == 1
        (per_chunk,) = counts
        assert all(n == round(p * 64 * 512) for n in per_chunk)


def test_quiet_idle_ticks_are_null_and_unchanged():
    pool = generate.make_pool(mix("quiet"), 64, 2048, 3)
    v, m = pool.value, pool.valid
    assert (v[~m] == 0).all()
    # consecutive idle ticks carry the same (value, valid): no change
    idle_pair = ~m[..., 1:] & ~m[..., :-1]
    assert (v[..., 1:][idle_pair] == v[..., :-1][idle_pair]).all()
    assert 0 < m.sum() < m.size * 1e-3


def test_random_walk_is_continuous_across_pool_chunks():
    pool = generate.make_pool(mix("paced"), 8, 128, 5)
    v, rows = pool.value, np.arange(8)
    value, valid = generate.stream_rows(pool, rows, pool.chunks)
    assert valid.all()
    steps = np.abs(np.diff(value, axis=1))
    assert steps.max() < 1.0          # step sigma 0.05: no jumps inside


def test_stream_rows_cycles_the_pool():
    pool = generate.make_pool(mix("busy"), 8, 64, 1)
    rows = np.array([1, 5])
    value, valid = generate.stream_rows(pool, rows, 2 * pool.chunks + 1)
    assert value.shape == (2, (2 * pool.chunks + 1) * 64)
    np.testing.assert_array_equal(value[:, :64], pool.value[0][rows])
    np.testing.assert_array_equal(
        value[:, pool.chunks * 64:(pool.chunks + 1) * 64], pool.value[0][rows])
    np.testing.assert_array_equal(valid[:, -64:], pool.valid[0][rows])
    assert pool.events(pool.chunks + 2) == pool.valid[2].sum()


def test_schedule():
    assert generate.schedule({"kind": "backlogged"}, 4, 3.0) is None
    fixed = {"kind": "open_loop",
             "phases": [{"seconds": 1.0, "ticks_per_s": 2.0}]}
    chunk, tick = generate.schedule(fixed, 4, 6.0)
    np.testing.assert_allclose(tick[0], [0.5, 1.0, 1.5, 2.0])
    np.testing.assert_allclose(chunk, [2.0, 4.0, 6.0])
    # bursts: 2 ticks/s for a second, then 6 ticks/s for a second, again
    burst = {"kind": "open_loop",
             "phases": [{"seconds": 1.0, "ticks_per_s": 2.0},
                        {"seconds": 1.0, "ticks_per_s": 6.0}]}
    chunk, tick = generate.schedule(burst, 4, 4.0)
    np.testing.assert_allclose(tick[0], [0.5, 1.0, 1 + 1 / 6, 1 + 2 / 6])
    np.testing.assert_allclose(chunk, [1 + 2 / 6, 2.0, 3 + 2 / 6, 4.0])


def test_unknown_kind_names_the_missing_file():
    with pytest.raises(ValueError, match="bench/kinds/pacing/poisson.py"):
        generate.schedule({"kind": "poisson"}, 4, 1.0)
