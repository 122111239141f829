"""The `olmoe-train-1chip` cell end to end at tiny size on the CPU, through
the benchmark's own command line (`chipbench/run.py --rehearsal`), and its
three new per-layer readers on hand-made records.

The manifest is BENCHMARK.json as it is with the cell's configuration and
traffic mix swapped for new tiny stand-ins
(chipbench/tests/rehearsal/data/configs/olmoe-tiny.json,
.../traffic/tiny-train-olmoe.json). chipbench's own rehearsal
(chipbench/tests, not part of tier-1) looks every configuration up in
rehearsal/data/tiny.json and asserts `reduced == []`; both are files the
benchmark already has, which PR 25 may not edit (PERF.md §7), so the new
cell is rehearsed from here. The numbers of a CPU run mean nothing and
are written nowhere."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "olmoe-train-1chip"
TINY = "chipbench/tests/rehearsal/data"


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    m = _load("BENCHMARK.json")
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    m["paths"] = [TINY]
    config["file"] = f"{TINY}/configs/olmoe-tiny.json"
    cell["traffic"] = "tiny-train-olmoe"
    m["workloads"], m["configs"] = [cell], [config]
    path = tmp_path_factory.mktemp("olmoe_rehearsal") / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return str(path)


# run.py ends by requiring that no /dev/shm/ray_tpu_session_* appeared
# during its run and stayed. That looks at the whole machine, and tier-1
# runs several test files, each with clusters of its own, at once: their
# sessions are not this run's leftovers. So the rehearsal runs run.py as
# __main__ with that one glob answering nothing, and everything else as it
# is (the chip run keeps the check: there run.py is alone on its machine).
RUN_PY = r"""
import glob, runpy, sys
_glob = glob.glob
glob.glob = lambda p, *a, **k: [] if str(p).startswith(
    "/dev/shm/ray_tpu_session_") else _glob(p, *a, **k)
sys.argv = ["chipbench/run.py"] + sys.argv[1:]
runpy.run_path("chipbench/run.py", run_name="__main__")
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "-c", RUN_PY,
         "--rehearsal", manifest_path, "--workload", CELL, "--seed", "3",
         "--seconds", "2.0", "--trace", str(trace)],
        capture_output=True, text=True, timeout=400, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    detail, line = lines[-2], lines[-1]
    assert line["correct"] is True, (line, detail)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    check = detail["checks"]["loss_vs_reference"]
    assert abs(check["got"] - check["want"]) <= check["tolerance"]
    declared = {m["name"] for m in _load("BENCHMARK.json")[
        "per_layer" if trace else "end_to_end"]
        if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) <= declared
    if trace:
        # The CPU has no Mosaic rows, so the kernel metrics are left out;
        # what the host clock gives is there.
        assert {"step_ms_p50", "time_to_first_step_s"} <= set(
            line["metrics"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_benchmark_lists_the_cell_under_the_metrics_issue_25_names():
    m = _load("BENCHMARK.json")
    listed = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
              if CELL in x.get("workloads", ())}
    # PR 33's split of set-up lists every cell (tests/test_run_timeline.py)
    split = {x["name"] for x in m["per_layer"] if x["moves"] == "setup_s"
             and x["name"] != "time_to_first_step_s"}
    assert len(split) == 10 and split <= listed      # PR 50: step_build_s
    assert listed - split == {
        "train_tokens_per_s", "time_to_first_step_s", "step_ms_p50", "mfu",
        "train_device_idle_share", "attn_fwd_kernel_ms_per_step",
        "attn_dq_kernel_ms_per_step", "attn_dkv_kernel_ms_per_step",
        "expert_gmm_ms_per_step", "expert_gmm_roofline",
        "attn_scoped_roofline"}
    config = next(c for c in m["configs"] if c["name"] == "olmoe-1b-7b")
    on_disk = _load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1


READERS = ("expert_gmm_ms_per_step", "expert_gmm_roofline",
           "attn_scoped_roofline")


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_on_an_empty_record(name):
    from chipbench import harness

    empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
    assert harness.reader(name).read(empty) is None


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made mosaic_by_name at the cell's real sizes: 4 traced
    steps, grouped matmuls 0.4 s, attention 0.2 s. By hand, at depth 2,
    16,384 tokens of 8 experts each, d 2048, f 1024:
    operations 2 x 9 x 2 x 131072 x 2048 x 1024 = 9.8956e12 -> 50.23 ms at
    197 TFLOP/s (bytes 36 x (131072 x 3072 + 64 x 2048 x 1024) = 1.9327e10
    -> 23.6 ms at 819 GB/s, the smaller); attention operations 2 x 6 x 2 x
    4 x 4096^2 x 2048 / 2 = 1.6493e12 -> 8.372 ms (bytes 2 x 12 x 4 x 4096
    x 2048 x 2 = 1.61e9 -> 1.97 ms)."""
    code = r"""
import json, sys
sys.path.insert(0, %r)
from chipbench import harness
record = {
    "config": json.load(open("chipbench/configs/olmoe-1b-7b.json")),
    "counters": {"global_batch": 4, "seq": 4096, "chips": 1,
                 "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}},
    "trace": {"steps": 4, "mosaic_by_name": {
        "mosaic:jvp_grouped_matmul_fwd_": 0.2,
        "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.1,
        "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.1,
        "mosaic:flash_attention_fwd": 0.08,
        "mosaic:flash_attention_dq": 0.05,
        "mosaic:flash_attention_dkv": 0.07}}}
out = {n: harness.reader(n).read(record) for n in %r}
assert "jax" not in sys.modules, "a reader imported jax"
print(json.dumps(out))
""" % (ROOT, READERS)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout)
    assert got["expert_gmm_ms_per_step"] == pytest.approx(100.0)
    flops = 2 * 9 * 2 * 131072 * 2048 * 1024
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.1)
    assert got["expert_gmm_roofline"] == pytest.approx(50.23, abs=0.01)
    attn = 2 * 6 * 2 * 4 * 4096 ** 2 * 2048 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.05)
    assert got["attn_scoped_roofline"] == pytest.approx(16.74, abs=0.01)
