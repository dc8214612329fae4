"""The trace reduction: busy union, gaps named by the host span that
covers them, and per-operation device time."""
import os

import pytest

import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_planes_by_hand():
    ms = 1_000_000
    data = {
        "host": [("bench.window", 0, 100 * ms),
                 ("bench.serve", 0, 60 * ms),
                 ("bench.egress", 60 * ms, 100 * ms)],
        "devices": {0: [("fusion.1 = f32[2]", 5 * ms, 20 * ms),
                        ("fusion.1 = f32[2]", 20 * ms, 30 * ms),
                        ("cond = (f32[2]) conditional", 40 * ms, 50 * ms),
                        ("fusion.2 = f32[2]", 42 * ms, 48 * ms),  # inside
                        ("copy = f32[2]", 95 * ms, 120 * ms)]},   # past end
    }
    s = trace.reduce_planes(data, [0])
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.040)        # 5-30, 40-50, 95-100
    assert s["devices"][0]["idle_share"] == pytest.approx(0.6)
    assert s["op_s"]["fusion.1"] == pytest.approx(0.025)
    assert s["op_s"]["cond"] == pytest.approx(0.004)      # self time
    assert s["op_s"]["fusion.2"] == pytest.approx(0.006)
    assert s["op_s"]["copy"] == pytest.approx(0.005)
    assert s["op_calls"]["fusion.1"] == 2
    # gaps 0-5 and 30-40 in serve; 50-95 is named by its middle (egress)
    assert s["gap_s"]["bench.serve"] == pytest.approx(0.015)
    assert s["gap_s"]["bench.egress"] == pytest.approx(0.045)
    assert s["breakdown"]["idle_gaps"][0][0] == "bench.egress"


def test_reduce_planes_averages_devices():
    ms = 1_000_000
    data = {"host": [("bench.window", 0, 10 * ms)],
            "devices": {0: [("a", 0, 10 * ms)], 1: [("a", 0, 5 * ms)]}}
    s = trace.reduce_planes(data, [0, 1])
    assert s["busy_s"] == pytest.approx(0.0075)
    assert s["devices"][1]["idle_share"] == pytest.approx(0.5)
    assert s["gap_s"] == {"bench.window": pytest.approx(0.0025)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes({"host": [], "devices": {}}, [0])


def test_recorded_trace():
    """A 0.3 s traced window of trend.paced on one TPU v5e: the device's
    busy time, the two window kernels (8192 symbols x 4 segments of 178
    ticks, windows of 20 and 50) at the top of its operations, and idle
    gaps named by the harness's spans."""
    import re

    import roofline
    s = trace.summarize(os.path.join(DATA, "trend_paced_small.xplane.pb"),
                        [0])
    assert s["window_s"] == pytest.approx(0.333685, abs=1e-6)
    assert s["busy_s"] == pytest.approx(0.239566, abs=1e-6)
    assert s["devices"][0]["ops"] == 168
    top = [name for name, _ in s["breakdown"]["device_ops"][:2]]
    assert all("jit_sliding_sum" in n for n in top)
    assert set(s["gap_s"]) == {"bench.wait", "bench.step", "bench.egress"}
    assert sum(s["gap_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    k = "%vmap_jit_sliding_sum__.3"
    assert s["op_calls"][k] == 4
    # (32768, 2, 384) f32 read once (one operand passed twice), (32768,
    # 2, 256) f32 written
    assert roofline.call_bytes(s["op_text"][k]) == 32768 * 2 * (384 + 256) * 4
    share = roofline.share(s, re.compile(r"jit_sliding_(sum|assoc)"), 819e9)
    assert 0 < share < 100
