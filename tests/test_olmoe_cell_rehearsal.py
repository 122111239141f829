"""The `olmoe-train-1chip` cell end to end at tiny size on the CPU, through
the benchmark's own command line (`chipbench/run.py --rehearsal`), and its
three new per-layer readers on hand-made records.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/olmoe-tiny.json and
.../traffic/tiny-train-olmoe.json; tests/cell_rehearsal.py has the
manifest, the runs and why the cell is rehearsed from here."""

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "olmoe-train-1chip"


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "olmoe-tiny",
                              "tiny-train-olmoe")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 3, trace)


def test_benchmark_lists_the_cell_under_the_metrics_issue_25_names():
    m = load("BENCHMARK.json")
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
    on_disk = load(config["file"])
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
    got = rehearsal.read_without_jax(READERS, {
        "config": load("chipbench/configs/olmoe-1b-7b.json"),
        "counters": {"global_batch": 4, "seq": 4096, "chips": 1,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_grouped_matmul_fwd_": 0.2,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.1,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.1,
            "mosaic:flash_attention_fwd": 0.08,
            "mosaic:flash_attention_dq": 0.05,
            "mosaic:flash_attention_dkv": 0.07}}})
    assert got["expert_gmm_ms_per_step"] == pytest.approx(100.0)
    flops = 2 * 9 * 2 * 131072 * 2048 * 1024
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.1)
    assert got["expert_gmm_roofline"] == pytest.approx(50.23, abs=0.01)
    attn = 2 * 6 * 2 * 4 * 4096 ** 2 * 2048 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.05)
    assert got["attn_scoped_roofline"] == pytest.approx(16.74, abs=0.01)
