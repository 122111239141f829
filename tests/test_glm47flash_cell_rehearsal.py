"""The `glm47flash-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tools its limits and counters are read with, what BENCHMARK.json and
the configuration's file say of it, and its readers on a hand-made record
at its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/glm47flash-tiny.json and
.../traffic/tiny-train-glm47flash.json (one dense layer, one expert layer
and the prediction module behind them, four heads of 24 + 8 | 32 over
latents of 64 and 48, experts 2 to 5 of 8 held beside a shared expert, two
sequences of 64); tests/cell_rehearsal.py has the manifest, the runs and
why the cell is rehearsed from here."""

import dataclasses
import json
import math
import os

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "glm47flash-train-1chip"
CONFIG = "chipbench/configs/glm-4.7-flash.json"
MIX = "chipbench/traffic/pretrain-glm47flash-b1-s16384.json"
# chipbench/limit_readings.py with three of the family's eleven faults to
# plant, one of the module's own lines, one of its loss and one of a
# config's number: the pass reads each fault's loss and kernel errors in a
# program of its own; the others are the chip's (PERF.md section 4).
KEPT_FAULTS = ("halves_swapped", "target_not_shifted", "mtp_loss_weight_1")


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "glm47flash-tiny",
                              "tiny-train-glm47flash")


def test_cell_runs_end_to_end_on_the_cpu(manifest_path):
    """The traced run: the loop, the comparison that decides `correct` (the
    step's summed loss against the reference's), the trace's reduction and
    every reader the cell is listed under."""
    detail, _ = rehearsal.run_cell(manifest_path, CELL, 2147483900, 1)
    # the sum L = CE + 0.3 CE' at a vocabulary of 512: about 1.3 ln 512
    assert 7.5 < detail["checks"]["loss_vs_reference"]["want"] < 8.7
    assert detail["checks"]["compiled_in_window"] == 0
    assert set(detail["end_to_end"]) == {"train_tokens_per_s"}


def test_limit_readings_reads_both_limits_and_a_fault_of_each_kind(
        manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and a planted fault
    of the module's lines, of its targets and of the loss's weight
    (KEPT_FAULTS), and the layers' own errors for the same; KERNEL_LIMIT
    lies far under each planted fault, and the weight at 1 is outside the
    loss's limit by 0.7 of a cross entropy."""
    from chipbench.families import glm4_moe_lite as family

    assert set(family.STRUCTURAL_FAULTS) == {
        "halves_swapped", "hnorm_left_out", "enorm_left_out",
        "embedding_of_this_token", "target_not_shifted", "mtp_loss_weight_1",
        "block_not_causal", "table_gradient_dropped",
        "scale_of_the_no_rope_width", "rope_on_the_no_rope_columns",
        "routed_scale_left_out"}
    _, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                         family, KEPT_FAULTS)
    worst = ranges["kernel_errors_worst"]
    # The limit is the chip's, set between the kernels' reading and the
    # all-bfloat16 forms' at the published sizes (PERF.md section 4): here
    # the program is the jax.numpy forms in bfloat16 at a toy size, which
    # read about the limit itself, and far under every fault.
    assert worst["program"][1] <= 2 * family.KERNEL_LIMIT
    for name in KEPT_FAULTS:
        assert worst[name][0] > 5 * family.KERNEL_LIMIT, (name, worst[name])
    assert ranges["off_reference"]["mtp_loss_weight_1"][0] == pytest.approx(
        0.7 * math.log(512), rel=0.05)
    assert ranges["off_reference"]["program"][1] <= ranges["tolerance"]


def test_step_counters_read_the_modules_row(manifest_path):
    """chipbench/step_counters.py at tiny size: `expert_rows_held` comes a
    row an expert layer and the module's block last, each read against the
    balanced count."""
    line = rehearsal.step_counters(manifest_path, CELL, 3, 3)
    # 2 x 64 tokens, 3 of 8 experts a token, 4 held: 192 rows a layer
    assert line["rows_balanced"] == 192
    low, high = line["rows_held_over_balanced"]
    assert 0.5 < low <= high < 1.5
    with open(os.path.join(rehearsal.ROOT, "chiprun_out",
                           f"step_counters_{CELL}.json")) as f:
        steps = json.load(f)[0]["per_step"]
    assert all(len(step["expert_rows_held"]) == 2 for step in steps)


def test_benchmark_lists_the_cell_under_the_metrics_issue_55_names():
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
        "expert_gmm_roofline"}
    # every list xing4-train-1chip is on, and no other
    order = [w["name"] for w in m["workloads"]]
    for x in (*m["end_to_end"], *m["per_layer"]):
        if "workloads" in x:
            assert (CELL in x["workloads"]) == (
                "xing4-train-1chip" in x["workloads"]), x["name"]
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
    # no per-layer metric of its own and no new reader: the kernels at the
    # new shape have their roofline in attn_scoped_roofline through the
    # family's counts (mtp_ms_per_step and mtp_loss wait for a benchmark PR)
    assert not [x["name"] for x in m["per_layer"]
                if x.get("workloads") == [CELL]]
    cell = m["workloads"][9]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "glm-4.7-flash", "pretrain-glm47flash-b1-s16384", 1)
    assert len(m["workloads"]) >= 10 and len(m["configs"]) >= 9
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][8]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert on_disk["source"] == config["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load(MIX)
    assert (mix["driver"], mix["global_batch"], mix["seq"], mix["mesh_dp"],
            mix["remat"], mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        "train", 1, 16384, 0, True, 8, 2, 1, 6, 3, 4, 1)
    assert "1e-4" in mix["optimizer"] and "0.3" in mix["optimizer"]
    assert "TO_FILL" not in json.dumps(mix)


def test_configuration_is_the_catalogs_but_the_three_keys_cut():
    """Every key of the catalog's entry at its value but depth, the experts
    held and the vocabulary; the prediction module kept; the published
    counts stated beside."""
    on_disk = load(CONFIG)
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256}
    assert {k: on_disk[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 38720}
    assert {k: on_disk[k] for k in cut} == cut
    assert set(on_disk["reduced_from"]) == set(cut) == set(on_disk["reduced"])
    assert on_disk["deployment_sizes"] == {
        "chips_sharing_a_layer": 4, "n_routed_experts": 64,
        "first_expert_held": 0, "vocab_size": 154880,
        "num_hidden_layers": 47}
    assumed = on_disk["assumed"]
    assert (assumed["latent_norm_eps"], assumed["mtp_loss_weight"],
            assumed["initializer_range"], assumed["balance_tokens"]) == (
        1e-6, 0.3, 0.02, 32768)
    assert assumed["bias_rounds"] % 8 == 0
    for key in ("layer_equations", "prediction_module", "why_mtp_loss_weight",
                "why_latent_norm_eps", "rope_form", "optimizer"):
        assert assumed[key], key
    # the rate: the issue's, the family's constant, stated in the file
    from chipbench.families import glm4_moe_lite
    assert glm4_moe_lite.LEARNING_RATE == 1e-4
    assert "1e-4" in assumed["optimizer"] and "xing4" in assumed["optimizer"]
    for said in ("trained and not served", "absent experts", "interleaved",
                 "e_score_correction_bias is not zero at the start",
                 "a quarter of their share", "eight times their share",
                 "not copies"):
        assert any(said in line for line in on_disk["departures"]), said
    for key in ("assumed", "departures", "deployment"):
        assert on_disk[key], key
    # the floors of a model_config cut: four layers after the dense one,
    # 16 >= 8 experts, a quarter >= an eighth of the vocabulary; no width
    # among the keys cut
    assert on_disk["num_hidden_layers"] - on_disk["first_k_dense_replace"] == 4
    assert on_disk["vocab_size"] * 4 == 154880
    assert not [k for k in on_disk["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    # and the program's own published config has the same widths
    from ray_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig
    cell = glm4_moe_lite.build(on_disk)
    full = Glm4MoeLiteConfig.glm_4_7_flash()
    assert dataclasses.replace(
        full, n_layers=5, experts_held=(0, 16), vocab_size=38720) == cell


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/glm4_moe_lite.py (the parent commit, with
    this benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("glm4_moe_lite.py",), "from .glm4_moe_lite import")
    assert "cannot run a glm4_moe_lite configuration" in proc.stderr


READERS = ("expert_gmm_ms_per_step", "expert_gmm_roofline",
           "attn_scoped_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps, grouped
    matmuls 0.2 s, attention 2.4 s, 11,000 tokens a second. By hand, for a
    balanced share (16,384 rows a layer, 5 layers with the module's):
    expert operations 5 x 9 x 2 x 16384 x 2048 x 1536 = 4.638e12 -> 23.5
    ms at 197 TFLOP/s; attention operations 6 calls x 2 x 16384^2 x 20 x 3
    x 512 / 2 = 4.947e13 -> 251.1 ms (bytes 6 x 6 x 16384 x 20 x 512 x 2 =
    1.208e10 -> 14.7 ms, the smaller)."""
    got = rehearsal.read_without_jax(READERS, {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "tokens_per_s": 11000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_grouped_matmul_fwd_": 0.1,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.05,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.05,
            "mosaic:flash_attention_fwd": 0.6,
            "mosaic:flash_attention_dq": 0.6,
            "mosaic:flash_attention_dkv": 1.2}}}, family="glm4_moe_lite")
    assert got["expert_gmm_ms_per_step"] == pytest.approx(50.0)
    flops = 5 * 9 * 2 * 16384 * 2048 * 1536
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.05)
    assert got["expert_gmm_roofline"] == pytest.approx(47.1, abs=0.05)
    attn = 6 * 2 * 16384 ** 2 * 20 * 3 * 512 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.6)
    assert got["attn_scoped_roofline"] == pytest.approx(41.9, abs=0.05)
    from chipbench.families import glm4_moe_lite
    assert got["mfu"] == pytest.approx(
        100 * glm4_moe_lite.train_flops_per_token(load(CONFIG), 16384)
        * 11000.0 / 197e12)
    assert got["mfu"] == pytest.approx(32.1, abs=0.1)
    assert all(0 < got[name] <= 100 for name in READERS[1:])
