"""The `xing4-train-1chip` cell end to end at tiny size on the CPU, through
the benchmark's own command line (`chipbench/run.py --rehearsal`), the tool
its limits are read with, what BENCHMARK.json and the configuration's file
say of it, and its readers on a hand-made record at its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/xing4-tiny.json and
.../traffic/tiny-train-xing4.json (one dense layer then one expert layer,
three streams, four heads of 32 + 16 | 24 over latents of 64 and 48,
experts 2 to 5 of 8 held beside a shared expert, two sequences of 64);
tests/cell_rehearsal.py has the manifest, the runs and why the cell is
rehearsed from here."""

import dataclasses

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "xing4-train-1chip"
CONFIG = "chipbench/configs/xing4.0-29b-a4b.json"
MIX = "chipbench/traffic/pretrain-xing4-b1-s16384.json"
# chipbench/limit_readings.py with two of the family's ten faults to plant,
# one of the latent layer's and one of the residual rule's: the pass reads
# each fault's loss and kernel errors in a program of its own; the others
# are the chip's (PERF.md section 4), and tests/test_xing4.py holds the
# latent layer's five to the layer.
KEPT_FAULTS = ("scale_without_mscale", "h_res_transposed")


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "xing4-tiny",
                              "tiny-train-xing4")


def test_cell_runs_end_to_end_on_the_cpu(manifest_path):
    """The traced run: the loop, the comparison that decides `correct`, the
    trace's reduction and every reader the cell is listed under."""
    detail, _ = rehearsal.run_cell(manifest_path, CELL, 2147483900, 1)
    assert detail["checks"]["compiled_in_window"] == 0
    assert set(detail["end_to_end"]) == {"train_tokens_per_s"}


def test_limit_readings_reads_both_limits_and_a_fault_of_each_kind(
        manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and a planted fault
    of the latent layer and of the residual rule (KEPT_FAULTS), and the
    layers' own errors for the same; KERNEL_LIMIT lies far under each
    planted fault."""
    from chipbench.families import xing4 as family

    assert set(family.STRUCTURAL_FAULTS) == {
        "rope_on_the_no_rope_columns", "latent_norm_left_out",
        "query_latent_norm_left_out", "frequencies_not_scaled",
        "scale_without_mscale", "h_res_not_normalised", "h_res_transposed",
        "h_post_without_its_2", "shared_expert_relu2",
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


def test_benchmark_lists_the_cell_under_the_metrics_issue_53_names():
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
    # every list lfm2moe-train-1chip is on but the two of its convolution
    order = [w["name"] for w in m["workloads"]]
    for x in (*m["end_to_end"], *m["per_layer"]):
        if "lfm2moe-train-1chip" in x.get("workloads", ()):
            assert (CELL in x["workloads"]) == (
                not x["name"].startswith("short_conv_")), x["name"]
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
    # no per-layer metric of its own: the kernels at the new shape have
    # their roofline in attn_scoped_roofline through the family's counts
    assert not [x["name"] for x in m["per_layer"]
                if x.get("workloads") == [CELL]]
    cell = m["workloads"][8]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "xing4.0-29b-a4b", "pretrain-xing4-b1-s16384", 1)
    assert len(m["workloads"]) >= 9 and len(m["configs"]) >= 8
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][7]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load(MIX)
    assert (mix["global_batch"], mix["seq"], mix["remat"],
            mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        1, 16384, True, 8, 2, 1, 6, 3, 4, 1)
    assert "1e-4" in mix["optimizer"]


def test_configuration_is_the_catalogs_but_the_five_keys_cut():
    """Every key of the catalog's entry at its value but depth, the dense
    layers, the experts held, the vocabulary and the prediction module; the
    published counts stated beside."""
    on_disk = load(CONFIG)
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: on_disk[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}
    assert {k: on_disk[k] for k in cut} == cut
    assert set(on_disk["reduced_from"]) == set(cut) == set(on_disk["reduced"])
    assert on_disk["deployment_sizes"] == {
        "chips_sharing_a_layer": 8, "n_routed_experts": 64,
        "first_expert_held": 0, "vocab_size": 131072,
        "num_hidden_layers": 40}
    assumed = on_disk["assumed"]
    assert (assumed["latent_norm_eps"], assumed["hc_alpha_init"],
            assumed["hc_res_init"], assumed["initializer_range"],
            assumed["balance_tokens"]) == (1e-6, 0.01, 8.0, 0.02, 32768)
    assert assumed["bias_rounds"] % 8 == 0
    for key in ("layer_equations", "hc_eps_place", "hc_clamp_place",
                "hc_ends", "why_hc_init", "rope_form", "optimizer"):
        assert assumed[key], key
    # the rate: the issue's, the family's constant, stated in the file
    from chipbench.families import xing4
    assert xing4.LEARNING_RATE == 1e-4
    assert "1e-4" in assumed["optimizer"]
    for said in ("multi-token-prediction", "absent experts", "interleaved",
                 "e_score_correction_bias is not zero at the start",
                 "an eighth of their share", "eight times their share",
                 "four [B, S, 3584] values"):
        assert any(said in line for line in on_disk["departures"]), said
    for key in ("assumed", "departures", "deployment"):
        assert on_disk[key], key
    # the floors of a model_config cut: four layers after the dense one,
    # 8 >= 8 experts, an eighth >= an eighth of the vocabulary
    assert on_disk["num_hidden_layers"] - on_disk["first_k_dense_replace"] == 4
    assert on_disk["vocab_size"] * 8 == 131072
    # and the program's own published config has the same widths
    from ray_tpu.models.xing4 import Xing4Config
    cell = xing4.build(on_disk)
    full = Xing4Config.xing4_29b_a4b()
    assert dataclasses.replace(
        full, n_layers=5, n_dense_layers=1, experts_held=(0, 8),
        vocab_size=16384) == cell


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/xing4.py (the parent commit, with this
    benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("xing4.py",), "from .xing4 import")
    assert "cannot run a xing4 configuration" in proc.stderr


READERS = ("expert_gmm_ms_per_step", "expert_gmm_roofline",
           "attn_scoped_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps, grouped
    matmuls 0.1 s, attention 2.4 s, 15,000 tokens a second. By hand, for a
    balanced share (8,192 rows a layer, 4 layers): expert operations 4 x 9
    x 2 x 8192 x 3584 x 1024 = 2.165e12 -> 11.0 ms at 197 TFLOP/s;
    attention operations 5 layers x 2 x 16384^2 x 32 x 3 x 320 / 2 =
    4.123e13 -> 209.3 ms (bytes 5 x 6 x 16384 x 32 x 320 x 2 = 1.0066e10
    -> 12.3 ms, the smaller)."""
    got = rehearsal.read_without_jax(READERS, {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "tokens_per_s": 15000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_grouped_matmul_fwd_": 0.05,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.025,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.025,
            "mosaic:flash_attention_fwd": 0.6,
            "mosaic:flash_attention_dq": 0.6,
            "mosaic:flash_attention_dkv": 1.2}}}, family="xing4")
    assert got["expert_gmm_ms_per_step"] == pytest.approx(25.0)
    flops = 4 * 9 * 2 * 8192 * 3584 * 1024
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.025)
    assert got["expert_gmm_roofline"] == pytest.approx(43.96, abs=0.05)
    attn = 5 * 2 * 16384 ** 2 * 32 * 3 * 320 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.6)
    assert got["attn_scoped_roofline"] == pytest.approx(34.9, abs=0.05)
    from chipbench.families import xing4
    assert got["mfu"] == pytest.approx(
        100 * xing4.train_flops_per_token(load(CONFIG), 16384)
        * 15000.0 / 197e12)
    assert got["mfu"] == pytest.approx(36.1, abs=0.1)
