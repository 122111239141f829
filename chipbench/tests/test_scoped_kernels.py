"""The per-kernel readers (layer_metrics/attn_{fwd,dq,dkv}_kernel_ms_per_step)
on a small trace recorded on the v5e after the program got its scopes
(recorded/scoped/, PR 23): a row a kernel, known answers, and the three
sum to the Mosaic time of the plane. On the trace recorded before the
scopes (recorded/tiny-train-v5e.xplane.pb, PR 22) they find nothing."""

import json
import os

import pytest

from chipbench import harness, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "recorded", "scoped")
READERS = ("attn_fwd_kernel_ms_per_step", "attn_dq_kernel_ms_per_step",
           "attn_dkv_kernel_ms_per_step")


def _record(path, steps):
    reduced = xplane.reduce(xplane.read_trace(path, "tpu"))
    return {"trace": {**reduced, "steps": steps}, "counters": {}}


def test_readers_on_the_recorded_scoped_trace():
    with open(os.path.join(SCOPED, "expected.json")) as f:
        want = json.load(f)
    record = _record(os.path.join(SCOPED, want["file"]), want["steps"])
    trace = record["trace"]
    assert trace["planes"] == ["/device:TPU:0"]
    assert trace["mosaic_by_name"] == {
        k: pytest.approx(v, rel=1e-9)
        for k, v in want["mosaic_by_name"].items()}
    got = {name: harness.reader(name).read(record) for name in READERS}
    for name in READERS:
        assert got[name] == pytest.approx(want["ms_per_step"][name],
                                          rel=1e-9), name
    # one plane: its Mosaic time is the three kernels', and nothing else
    assert sum(got.values()) == pytest.approx(
        1e3 * trace["mosaic_s"] / want["steps"], rel=1e-9)
    assert sum(got.values()) == pytest.approx(
        harness.reader("attn_kernel_ms_per_step").read(record), rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_the_scopes(name):
    record = _record(os.path.join(HERE, "recorded",
                                  "tiny-train-v5e.xplane.pb"), 2)
    assert record["trace"]["mosaic_s"] > 0
    assert harness.reader(name).read(record) is None
    assert harness.reader(name).read({"trace": {}, "counters": {}}) is None
