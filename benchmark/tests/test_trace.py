"""The trace reduction: busy union, idle share, per-program device time and
the naming of idle gaps, on a hand-made trace and on a small one recorded on a
TPU v5e (``data/train_tiny.xplane.pb.gz``: the train cell at the tiny test size,
tests/tiny.py)."""

import os

import pytest

from benchmark import trace

US = 1000


def handmade():
    dev = {"modules": [(0, 100 * US, "jit_step(1)"),
                       (150 * US, 250 * US, "jit_step(1)"),
                       (400 * US, 420 * US, "jit_other(2)")],
           "ops": [(0, 60 * US, "fusion.1"), (50 * US, 100 * US, "copy.2"),
                   (150 * US, 250 * US, "fusion.1"),
                   (400 * US, 410 * US, "copy.2"),
                   (415 * US, 420 * US, "fusion.1")]}
    host = [(90 * US, 160 * US, "data_wait"), (0, 500 * US, "train"),
            (240 * US, 405 * US, "host_fetch")]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_reduce_handmade():
    r = trace.reduce(handmade(), window_s=500e-6)
    assert r["busy_s"] == pytest.approx(215e-6)          # 100 + 100 + 10 + 5
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.57)
    assert r["programs"]["jit_step"] == pytest.approx([100e-6, 100e-6])
    assert r["programs"]["jit_other"] == pytest.approx([20e-6])
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(165e-6)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["data_wait___after_jit_step_before_jit_step"] == \
        pytest.approx(50e-6)
    assert gaps["host_fetch___after_jit_step_before_jit_other"] == \
        pytest.approx(150e-6)
    assert gaps["gaps_under_20_us_between_ops"] == pytest.approx(5e-6)


def test_reduce_nothing_on_the_device_is_nothing():
    assert trace.reduce({"devices": {}, "host": [(0, 5, "x")]}) is None


def test_recorded_tpu_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "train_tiny.xplane.pb.gz")
    r = trace.reduce(trace.load(path))
    steps = r["programs"]["jit_train_step"]
    assert len(steps) >= 3 and all(0 < s < 0.1 for s in steps)
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert any("before_jit_train_step" in name for name, _ in r["idle_gaps"])
