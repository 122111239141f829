"""The trace reduction on hand-made intervals, where every answer is
known, and on the small recorded trace kept beside this file."""

import glob
import os

import pytest

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000.0   # the reduction works in nanoseconds


def _trace():
    """Two chips, 100 us window. Chip 0: a kernel 0-30, an all-reduce
    25-45 (5 us under the kernel, 15 us exposed), a fusion 60-100, so
    busy = 85 us and one idle gap 45-60; the host was in bench.report
    for 50-58 of it. Chip 1: the same, with the all-reduce wholly hidden."""
    kernel = '%jvp__.1 = (bf16[16,128,16]) custom-call(bf16[16,128,16] ' \
             '%copy.1), custom_call_target="tpu_custom_call"'
    reduce_ = "%all-reduce.7 = bf16[768,768] all-reduce(bf16[768,768] %x)"
    fusion = "%fusion.3 = bf16[8,8] fusion(bf16[8,8] %all-reduce.7)"
    chip0 = [(kernel, 0 * US, 30 * US), (reduce_, 25 * US, 20 * US),
             (fusion, 60 * US, 40 * US)]
    chip1 = [(kernel, 0 * US, 50 * US), (reduce_, 25 * US, 20 * US),
             (fusion, 60 * US, 40 * US)]
    return {"devices": {"/device:TPU:0": chip0, "/device:TPU:1": chip1},
            "modules": {"/device:TPU:0": [
                ("jit_train_step(123)", 0 * US, 45 * US),
                ("jit_train_step(123)", 60 * US, 40 * US)]},
            "spans": [("bench.report", 50 * US, 8 * US)]}


def test_interval_arithmetic():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
    assert xplane.total([[0, 3], [5, 8]]) == 6
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == \
        [[0, 2], [3, 5]]
    assert xplane.subtract([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]
    assert xplane.subtract([[0, 4]], [[0, 4]]) == []
    assert xplane.is_collective("%all-reduce-start.3 = f32[8] all-reduce-"
                                "start(f32[8] %p)")
    # an operand named after a collective does not make an op one
    assert not xplane.is_collective("%fusion.12 = f32[8] fusion(f32[8] "
                                    "%all-reduce.3)")
    assert xplane.short_name("%fusion.12 = f32[8] fusion(..)") == "fusion.12"
    assert xplane.short_name("dot_general.1") == "dot_general.1"


def test_reduce_gives_the_known_idle_kernel_and_collective_times():
    r = xplane.reduce(_trace())
    us = 1e-6
    assert r["window_s"] == pytest.approx(100 * us)
    # chip 0 is busy 85 us, chip 1 is busy 90 us
    assert r["busy_s"] == pytest.approx(87.5 * us)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.125)
    assert r["mosaic_s"] == pytest.approx(40 * us)
    assert r["mosaic_by_name"] == {"mosaic:jvp__": pytest.approx(30 * us)}
    assert r["collective_s"] == pytest.approx(20 * us)
    # 15 us exposed on chip 0, none on chip 1
    assert r["collective_exposed_s"] == pytest.approx(7.5 * us)
    assert r["device_ops"][0] == ["fusion.3", pytest.approx(40 * us)]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.report->jit_train_step"] == pytest.approx(8 * us)
    assert gaps["no-span->jit_train_step"] == pytest.approx(7 * us)
    assert sum(gaps.values()) == pytest.approx(15 * us)


def test_reduce_of_a_trace_without_device_events_is_empty():
    assert xplane.reduce({"devices": {}, "modules": {}, "spans": []}) == {}


def test_recorded_trace_reduces_to_the_values_read_by_hand():
    """recorded/*.xplane.pb: taken on the chip tool's v5e (PR 22) from
    the rehearsal's tiny train cell; the expected values were read from
    it by hand once (recorded/README.txt)."""
    import json
    files = glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb"))
    if not files:
        pytest.skip("no recorded trace beside the tests")
    with open(os.path.join(HERE, "recorded", "expected.json")) as f:
        expected = json.load(f)
    for path in files:
        want = expected[os.path.basename(path)]
        r = xplane.reduce(xplane.read_trace(path, "tpu"))
        assert r["planes"] == want["planes"]
        assert r["n_device_events"] == want["n_device_events"]
        for key in ("window_s", "busy_s", "mosaic_s", "collective_s",
                    "collective_exposed_s"):
            assert r[key] == pytest.approx(want[key], rel=1e-9), key
        assert set(r["mosaic_by_name"]) == set(want["mosaic_by_name"])
