"""The `trinity-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tools its limits and counters are read with, what BENCHMARK.json and
the traffic mix's file say of it, and its readers on a hand-made record at
its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/trinity-tiny.json and
.../traffic/tiny-train-trinity.json (five layers, windowed x 3, full,
windowed under a window of 16; 6 query heads over 2 of 16; a dense layer,
then experts 2 to 5 of 16 held beside a shared one; two sequences of 64,
four windows long); tests/cell_rehearsal.py has the manifest, the runs and
why the cell is rehearsed from here. tests/test_afmoe.py holds the layers
to the reference and plants all twelve faults; the pass here plants
none."""

import json

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "trinity-train-1chip"
CONFIG = "chipbench/configs/trinity-large-preview.json"
MIX = "chipbench/traffic/pretrain-trinity-b1-s8192.json"
KEPT_FAULTS = ()


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "trinity-tiny",
                              "tiny-train-trinity")


def test_cell_runs_end_to_end_on_the_cpu(manifest_path):
    """The traced run: the loop on the ring, the comparison that decides
    `correct` (the loss against the reference's), the driver's falling-loss
    check, the trace's reduction and every reader the cell is listed
    under."""
    # (a window of 6 s, as keyevl2's: the first traced group of this cell's
    # tiny steps is 0.6 s alone)
    detail, _ = rehearsal.run_cell(manifest_path, CELL, 2147483900, 1,
                                   seconds=6.0)
    checks = detail["checks"]
    assert 6.0 < checks["loss_vs_reference"]["want"] < 6.5   # ln 512 = 6.24
    assert checks["last_loss"] < checks["first_loss"]
    assert checks["compiled_in_window"] == 0
    assert set(detail["end_to_end"]) == {"train_tokens_per_s"}


def test_limit_readings_reads_both_limits(manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference and the all-bfloat16 reference, and the layers'
    own errors for the same, every value of the four groups."""
    from chipbench.families import afmoe as family

    rows, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                            family, KEPT_FAULTS)
    errors = rows[0]["kernel_errors"]
    attention = ("out", "dx", *("d" + n for n in family._ATTN_NAMES))
    assert set(errors["program"]) == {
        *(f"win_{v}" for v in attention), *(f"full_{v}" for v in attention),
        *(f"moe_{v}" for v in family._MOE_VALUES),
        "blk_out", *(f"blk_d{n}" for n in family._BLK_NAMES)}
    # The limit is the chip's, set at the published sizes (PERF.md section
    # 4): here the program is the jax.numpy forms in bfloat16 at a toy
    # size, which read some times the limit itself.
    assert max(errors["program"].values()) <= 8 * family.KERNEL_LIMIT
    assert ranges["off_reference"]["program"][1] <= ranges["tolerance"]


def test_step_counters_read_a_row_a_layer(manifest_path):
    """chipbench/step_counters.py at tiny size: `expert_rows_held` comes a
    row an expert layer, each read against the balanced count."""
    import os
    line = rehearsal.step_counters(manifest_path, CELL, 3, 3)
    # 128 tokens, 3 of 16 experts a token, 4 held: 96 rows a layer
    assert line["rows_balanced"] == 96
    low, high = line["rows_held_over_balanced"]
    assert 0.5 < low <= high < 1.5
    with open(os.path.join(rehearsal.ROOT, "chiprun_out",
                           f"step_counters_{CELL}.json")) as f:
        steps = json.load(f)[0]["per_step"]
    assert all(len(step["expert_rows_held"]) == 4 for step in steps)


def test_benchmark_lists_the_cell_under_the_metrics_issue_65_names():
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
        "expert_gmm_roofline", "window_attn_ms_per_step",
        "window_attn_roofline"}
    order = [w["name"] for w in m["workloads"]]
    own = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in own] == ["window_attn_ms_per_step",
                                        "window_attn_roofline"]
    assert m["per_layer"][-2:] == own
    for x in own:
        assert (x["layer"], x["moves"], x["source"]) == (
            "kernels", "train_tokens_per_s", "device_trace")
    assert [(x["unit"], x["better"]) for x in own] == [("ms", "lower"),
                                                       ("%", "higher")]
    for x in (*m["end_to_end"], *m["per_layer"]):
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
            assert x["workloads"][-1] == CELL
    assert len(m["workloads"]) == 13 and len(m["configs"]) == 12
    cell = m["workloads"][12]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "trinity-large-preview", "pretrain-trinity-b1-s8192", 1)
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][11]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert on_disk["source"] == config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/"
        "config.json")
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load(MIX)
    assert (mix["driver"], mix["global_batch"], mix["seq"], mix["mesh_dp"],
            mix["remat"], mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        "train", 1, 8192, 0, True, 8, 2, 1, 6, 3, 4, 1)
    assert "TO BE FILLED" not in json.dumps(mix)
    from chipbench.families import afmoe as family
    rate = {1e-4: "1e-4", 1e-5: "1e-5", 1e-6: "1e-6"}[family.LEARNING_RATE]
    assert f"constant {rate}" in mix["optimizer"]
    assert f"constant {rate}" in on_disk["assumed"]["optimizer"]


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/afmoe.py (the parent commit, with this
    benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("afmoe.py",), "from .afmoe import")
    assert "cannot run an afmoe configuration" in proc.stderr


READERS = ("window_attn_ms_per_step", "window_attn_roofline",
           "attn_fwd_kernel_ms_per_step", "attn_scoped_roofline",
           "expert_gmm_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps, the
    banded calls 0.24 s, the full layer's three 0.08 s, the grouped matmuls
    0.04 s, 12,000 tokens a second. By hand: the band's operations 4 layers
    x 6 x 2 x 25,167,872 pairs x 48 x 128 = 7.42e12 -> 37.7 ms at 197
    TFLOP/s, over its bytes' 1.3 ms: 62.8% of 60 ms. The banded rows are
    read by the three kernel readers too (a substring), the full rows not
    by the two new ones."""
    from chipbench.families import afmoe as family

    record = {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 8192, "chips": 1,
                     "tokens_per_s": 12000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:flash_attention_fwd_window": 0.06,
            "mosaic:flash_attention_dq_window": 0.06,
            "mosaic:flash_attention_dkv_window": 0.12,
            "mosaic:flash_attention_fwd": 0.02,
            "mosaic:flash_attention_dq": 0.02,
            "mosaic:flash_attention_dkv": 0.04,
            "mosaic:jvp_grouped_matmul_fwd_": 0.02,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.01,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.01}}}
    got = rehearsal.read_without_jax(READERS, record, family="afmoe")
    assert family.WINDOW_KERNEL_ROWS == (
        "flash_attention_fwd_window", "flash_attention_dq_window",
        "flash_attention_dkv_window")
    assert got["window_attn_ms_per_step"] == pytest.approx(60.0)
    assert got["attn_fwd_kernel_ms_per_step"] == pytest.approx(20.0)
    flops = family.window_attention_flops(load(CONFIG), 1, 8192)
    assert flops == 4 * 6 * 2 * 25_167_872 * 48 * 128
    assert flops / 197e12 > family.window_attention_bytes(
        load(CONFIG), 1, 8192) / 819e9
    assert got["window_attn_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.06)
    assert got["window_attn_roofline"] == pytest.approx(62.8, abs=0.1)
    all_flops = family.attention_kernel_flops(load(CONFIG), 1, 8192)
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (all_flops / 197e12) / 0.08)
    # the experts' bytes bind at a thirty-second of a deployment's rows
    assert family.expert_matmul_flops(load(CONFIG), 8192) \
        == 4 * 9 * 2 * 1024 * 3072 * 3072
    gmm_bytes = 4 * 9 * 2 * (1024 * (3072 + 3072) + 8 * 3072 * 3072)
    assert family.expert_matmul_bytes(load(CONFIG), 8192) == gmm_bytes
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (gmm_bytes / 819e9) / 0.01)
    assert got["mfu"] == pytest.approx(
        100 * family.train_flops_per_token(load(CONFIG), 8192)
        * 12000.0 / 197e12)
    assert all(0 < got[name] <= 100 for name in READERS if name != READERS[0]
               and "ms" not in name)
    # on a record with no such row (another program's trace, or the parent's)
    # and under a family that names no such rows the two new readers read
    # nothing and do not raise
    record["trace"]["mosaic_by_name"] = {"mosaic:flash_attention_fwd": 0.3}
    bare = rehearsal.read_without_jax(READERS[:2], record)
    assert bare == {"window_attn_ms_per_step": None,
                    "window_attn_roofline": None}
    record["trace"]["mosaic_by_name"][
        "mosaic:flash_attention_fwd_window"] = 0.2
    record["config"] = load("chipbench/configs/phi-4-mini-flash-reasoning"
                            ".json")
    assert rehearsal.read_without_jax(READERS[:2], record) == bare
