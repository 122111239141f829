"""The `keyevl2-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tools its limits and counters are read with, what BENCHMARK.json and
the traffic mix's file say of it, and its readers on a hand-made record at
its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/keyevl2-tiny.json and
.../traffic/tiny-train-keyevl2.json (two sparse-attention layers of four
heads of 32 over two, an indexer of two heads of 16 naming 64 keys a query,
experts 4 to 7 of 8 held under the softmax router, placed, one sequence of 128 as
the ring's one batch); tests/cell_rehearsal.py has the manifest, the runs
and why the cell is rehearsed from here. tests/test_keye_vl2.py plants all
twelve faults in a layer's program; the pass here keeps one of each
limit's."""

import json
import os

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "keyevl2-train-1chip"
CONFIG = "chipbench/configs/keye-vl-2.0-30b-a3b.json"
MIX = "chipbench/traffic/pretrain-keyevl2-b1-s16384.json"
# chipbench/limit_readings.py with one of the family's twelve faults to
# plant, the selection's size (the kernels' limit sees it, the loss hardly):
# the pass reads a fault's loss and kernel errors in a program of its own.
KEPT_FAULTS = ("topk_1024",)


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "keyevl2-tiny",
                              "tiny-train-keyevl2")


def test_cell_runs_end_to_end_on_the_cpu(manifest_path):
    """The traced run: the loop on the ring's one batch, the comparison that
    decides `correct` (the step's summed loss against the reference's), the
    driver's falling-loss check of a batch against itself, the trace's
    reduction and every reader the cell is listed under."""
    # (a window of 6 s: the first traced group of two tiny steps is 0.4 s
    # alone, the profiler's start in it, and 2 s leave it five times of room
    # on a machine whose cores five other workers share; the test failed in
    # the driver's run of PR 64's tree and passes alone (22 s) and under six
    # workers of rehearsals here either way: load, not the tree)
    detail, _ = rehearsal.run_cell(manifest_path, CELL, 2147483900, 1,
                                   seconds=6.0)
    checks = detail["checks"]
    # L = CE + 0.001 balance + two layers' L_I at a vocabulary of 512
    assert 6.3 < checks["loss_vs_reference"]["want"] < 6.9
    assert checks["last_loss"] < checks["first_loss"]
    assert checks["compiled_in_window"] == 0
    assert set(detail["end_to_end"]) == {"train_tokens_per_s"}


def test_limit_readings_reads_both_limits_and_a_fault_of_each(manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and a planted
    fault, and the layers' own errors for the same; the planted fault is
    far outside KERNEL_LIMIT and far over the program's own reading."""
    from chipbench.families import keye_vl2 as family

    _, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                         family, KEPT_FAULTS)
    worst = ranges["kernel_errors_worst"]
    # The limit is the chip's, set at the published sizes (PERF.md section
    # 4): here the program is the jax.numpy forms in bfloat16 at a toy
    # size, which read about the limit itself, and far under every fault.
    assert worst["program"][1] <= 8 * family.KERNEL_LIMIT
    for name in KEPT_FAULTS:
        assert worst[name][0] > 5 * family.KERNEL_LIMIT, (name, worst[name])
        assert worst[name][0] > 4 * worst["program"][1]
    assert ranges["off_reference"]["program"][1] <= ranges["tolerance"]


def test_step_counters_read_a_row_a_layer(manifest_path):
    """chipbench/step_counters.py at tiny size: `expert_rows_held` comes a
    row a layer, each read against the balanced count, with no selection
    bias to report."""
    line = rehearsal.step_counters(manifest_path, CELL, 3, 3)
    # 128 tokens, 3 of 8 experts a token, 4 held: 192 rows a layer
    assert line["rows_balanced"] == 192
    low, high = line["rows_held_over_balanced"]
    assert 0.5 < low <= high < 1.5
    assert line["router_bias_abs_max"] == 0.0
    with open(os.path.join(rehearsal.ROOT, "chiprun_out",
                           f"step_counters_{CELL}.json")) as f:
        steps = json.load(f)[0]["per_step"]
    assert all(len(step["expert_rows_held"]) == 2 for step in steps)


def test_benchmark_lists_the_cell_under_the_metrics_issue_60_names():
    m = load("BENCHMARK.json")
    listed = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
              if CELL in x.get("workloads", ())}
    split = {x["name"] for x in m["per_layer"] if x["moves"] == "setup_s"
             and x["name"] != "time_to_first_step_s"}
    assert len(split) == 10 and split <= listed
    assert listed - split == {
        "train_tokens_per_s", "time_to_first_step_s", "step_ms_p50", "mfu",
        "train_device_idle_share", "attn_fwd_kernel_ms_per_step",
        "attn_dq_kernel_ms_per_step", "attn_dkv_kernel_ms_per_step",
        "attn_scoped_roofline", "expert_gmm_ms_per_step",
        "expert_gmm_roofline", "sparse_index_ms_per_step",
        "sparse_index_roofline"}
    # every list glm47flash-train-1chip is on and its own two, no other
    order = [w["name"] for w in m["workloads"]]
    own = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in own] == ["sparse_index_ms_per_step",
                                        "sparse_index_roofline"]
    # appended by PR 60, and only appended after since
    at = m["per_layer"].index(own[0])
    assert m["per_layer"][at:at + 2] == own
    for x in own:
        assert (x["layer"], x["moves"], x["source"]) == (
            "kernels", "train_tokens_per_s", "device_trace")
    assert (own[0]["unit"], own[1]["unit"]) == ("ms", "%")
    for x in (*m["end_to_end"], *m["per_layer"]):
        if "workloads" in x and x not in own:
            assert (CELL in x["workloads"]) == (
                "glm47flash-train-1chip" in x["workloads"]), x["name"]
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            # (the cell is each list's last until a later PR appends its own)
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
    cell = m["workloads"][10]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "keye-vl-2.0-30b-a3b", "pretrain-keyevl2-b1-s16384", 1)
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][9]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    assert on_disk["source"] == config["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load(MIX)
    assert (mix["driver"], mix["global_batch"], mix["seq"], mix["mesh_dp"],
            mix["remat"], mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        "train", 1, 16384, 0, True, 1, 2, 1, 6, 3, 4, 1)
    assert "1e-6" in mix["optimizer"] and "why_ring_batches" in mix
    assert "TO BE FILLED" not in json.dumps(mix)


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/keye_vl2.py (the parent commit, with
    this benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("keye_vl2.py", "sparse_index.py"),
        "from .keye_vl2 import")
    assert "cannot run a keye_vl2 configuration" in proc.stderr


READERS = ("sparse_index_ms_per_step", "sparse_index_roofline",
           "attn_scoped_roofline", "expert_gmm_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps, the
    indexer's two kernels 0.24 s, attention's three 1.2 s, the grouped
    matmuls 0.2 s, 12,000 tokens a second (`mfu` 13.0%). By hand: the indexer's operations
    6 layers x 3 products x 2 x 134,225,920 pairs x 16 x 64 = 4.948e12 ->
    25.1 ms at 197 TFLOP/s (bytes 6 x (3 x 16384 x 2240 + 8 x 134,225,920)
    = 7.1e9 -> 8.7 ms, the smaller); attention's over the SELECTED pairs 6
    x 6 x 2 x 31,458,304 x 4096 = 9.277e12 -> 47.1 ms; the experts' 6 x 9 x
    2 x 16384 x 2048 x 768 = 2.783e12 -> 14.1 ms. Rows of other scopes
    (`sparse_select`, `sparse_target` are XLA's and no Mosaic row) are not
    the indexer's."""
    from chipbench.families import keye_vl2 as family

    record = {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "tokens_per_s": 12000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:sparse_index_fwd": 0.08,
            "mosaic:jvp_sparse_index_bwd_": 0.16,
            "mosaic:jvp_grouped_matmul_fwd_": 0.1,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.05,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.05,
            "mosaic:flash_attention_fwd": 0.3,
            "mosaic:flash_attention_dq": 0.3,
            "mosaic:flash_attention_dkv": 0.6}}}
    got = rehearsal.read_without_jax(READERS, record, family="keye_vl2")
    assert got["sparse_index_ms_per_step"] == pytest.approx(60.0)
    flops = 6 * 3 * 2 * 134_225_920 * 1024
    assert family.sparse_index_flops(load(CONFIG), 1, 16384) == flops
    assert family.sparse_index_bytes(load(CONFIG), 1, 16384) \
        == 6 * (3 * 16384 * 2240 + 8 * 134_225_920)
    assert got["sparse_index_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.06)
    assert got["sparse_index_roofline"] == pytest.approx(41.9, abs=0.05)
    attn = 6 * 6 * 2 * 31_458_304 * 4096
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.3)
    assert got["attn_scoped_roofline"] == pytest.approx(15.7, abs=0.05)
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (6 * 9 * 2 * 16384 * 2048 * 768 / 197e12) / 0.05)
    assert got["mfu"] == pytest.approx(
        100 * family.train_flops_per_token(load(CONFIG), 16384)
        * 12000.0 / 197e12)
    assert got["mfu"] == pytest.approx(13.0, abs=0.1)
    assert all(0 < got[name] <= 100 for name in READERS[1:])
    # on a record with no such row (the parent's program, another family's
    # cell) the two new readers read nothing and do not raise
    record["trace"]["mosaic_by_name"] = {"mosaic:flash_attention_fwd": 0.3}
    bare = rehearsal.read_without_jax(READERS[:2], record)
    assert bare == {"sparse_index_ms_per_step": None,
                    "sparse_index_roofline": None}
    record["config"] = load("chipbench/configs/gpt2-small.json")
    assert rehearsal.read_without_jax(READERS[:2], record) == bare
