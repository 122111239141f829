"""The `nemotron3nano-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tool its limits are read with, what BENCHMARK.json and the
configuration's file say of it, and the readers it shares with the OLMoE
and granite cells on a hand-made record at its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/nemotron-tiny.json and
.../traffic/tiny-train-nemotron.json (MEM*E, two groups, experts 2 to 5 of
8 held, chunks of 8, one sequence of 128); tests/cell_rehearsal.py has the
manifest, the runs and why the cell is rehearsed from here."""

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "nemotron3nano-train-1chip"
CONFIG = "chipbench/configs/nemotron-3-nano-30b-a3b.json"
# chipbench/limit_readings.py with two of the family's six faults to plant,
# one of the grouped scan's and one of the held experts': the pass reads
# each fault's loss and kernel errors in a program of its own. All six are
# planted in-process, on the model's loss and on the layers
# (tests/test_nemotron_h.py::test_a_planted_fault_moves_the_models_loss[*],
# ::test_the_cell_holds_the_new_layers_to_a_limit_of_their_own[interpreted-*]).
KEPT_FAULTS = ("group_read_by_wrong_heads", "shared_expert_dropped")


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "nemotron-tiny",
                              "tiny-train-nemotron")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 2147483900, trace)


def test_limit_readings_reads_both_limits_and_a_fault_of_each_kind(
        manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and a planted fault
    of the grouped scan and of the held experts (KEPT_FAULTS), and the new
    layers' own errors for the same; KERNEL_LIMIT lies between the program
    and every planted fault."""
    from chipbench.families import nemotron_h as family

    assert set(family.STRUCTURAL_FAULTS) == {
        "group_read_by_wrong_heads", "norm_over_all_channels",
        "bias_added_to_the_weights", "shared_expert_dropped",
        "absent_rows_computed", "routed_scale_dropped"}
    _, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                         family, KEPT_FAULTS)
    worst = ranges["kernel_errors_worst"]
    assert worst["program"][1] <= family.KERNEL_LIMIT
    for name in KEPT_FAULTS:
        assert worst[name][0] > family.KERNEL_LIMIT, (name, worst[name])
    # at this size the all-bfloat16 forms lie above the program and about
    # the limit; the chip's reading at the published sizes is PERF.md's
    assert worst["all_bfloat16"][0] > worst["program"][1]


def test_step_counters_reads_the_held_rows_of_every_step(manifest_path):
    """chipbench/step_counters.py end to end at tiny size: the step at the
    default optimizer, its counters fetched a step; the held experts' rows
    stay near the balanced count the operations are reckoned for."""
    run = rehearsal.step_counters(manifest_path, CELL, 2147483900, 6)
    assert run["steps"] == 6 and run["rows_balanced"] > 0
    low, high = run["rows_held_over_balanced"]
    assert 0.8 < low <= high < 1.2, run
    assert run["loss_first_last"][1] < run["loss_first_last"][0]


def test_benchmark_lists_the_cell_under_the_metrics_issue_45_names():
    m = load("BENCHMARK.json")
    listed = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
              if CELL in x.get("workloads", ())}
    split = {x["name"] for x in m["per_layer"] if x["moves"] == "setup_s"
             and x["name"] != "time_to_first_step_s"}
    assert len(split) == 10 and split <= listed      # PR 50: step_build_s
    assert listed - split == {
        "train_tokens_per_s", "time_to_first_step_s", "step_ms_p50", "mfu",
        "train_device_idle_share", "attn_fwd_kernel_ms_per_step",
        "attn_dq_kernel_ms_per_step", "attn_dkv_kernel_ms_per_step",
        "attn_scoped_roofline", "ssm_scan_ms_per_step", "ssm_scan_roofline",
        "expert_gmm_ms_per_step", "expert_gmm_roofline"}
    order = [w["name"] for w in m["workloads"]]
    for x in (*m["end_to_end"], *m["per_layer"]):
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
    cell = m["workloads"][6]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "nemotron-3-nano-30b-a3b", "pretrain-nemotron3nano-b1-s16384",
        1)
    assert len(m["workloads"]) >= 7 and len(m["configs"]) >= 6
    config = m["configs"][5]
    on_disk = load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load("chipbench/traffic/pretrain-nemotron3nano-b1-s16384.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"],
            mix["ring_batches"], mix["fetch_lag_groups"],
            mix["median_over_groups"]) == (1, 16384, True, 8, 1, 6)


def test_configuration_is_the_catalogs_but_the_four_keys_cut():
    """Every key of the catalog's entry at its value but depth, pattern,
    experts held and vocabulary; the published counts stated beside."""
    on_disk = load(CONFIG)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: on_disk[k] for k in published} == published
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 16, "vocab_size": 16384}
    assert {k: on_disk[k] for k in cut} == cut
    assert set(on_disk["reduced_from"]) == set(cut) == set(on_disk["reduced"])
    full = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert full.startswith(on_disk["hybrid_override_pattern"])
    assert on_disk["reduced_from"]["hybrid_override_pattern"].startswith(full)
    assert on_disk["deployment_sizes"] == {
        "chips_sharing_a_layer": 8, "n_routed_experts": 128,
        "first_expert_held": 0, "vocab_size": 131072,
        "num_hidden_layers": 52}
    assert any("e_score_correction_bias is not zero at the start" in line
               for line in on_disk["departures"])
    for key in ("assumed", "departures", "deployment"):
        assert on_disk[key], key
    # the floors of a model_config cut: a whole period and nine layers,
    # 16 >= 8 experts, an eighth of the vocabulary
    assert set(on_disk["hybrid_override_pattern"]) == set("ME*")
    assert on_disk["vocab_size"] * 8 == 131072


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/nemotron_h.py (the parent commit, with this
    benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("nemotron_h.py",))
    assert "cannot run a nemotron-h configuration" in proc.stderr


READERS = ("expert_gmm_ms_per_step", "expert_gmm_roofline",
           "ssm_scan_ms_per_step", "ssm_scan_roofline",
           "attn_scoped_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps,
    grouped matmuls 0.04 s, scans 0.4 s, attention 0.4 s, 40,000 tokens a
    second. By hand, for a balanced share (12,288 rows a layer, 4 layers):
    expert operations 4 x 6 x 2 x 12288 x 2688 x 1856 = 2.9425e12 -> 14.94
    ms at 197 TFLOP/s (bytes 4 x 6 x 2 x (12288 x 4544 + 16 x 2688 x 1856)
    = 6.51e9 -> 7.95 ms at 819 GB/s, the smaller); the scan's bytes 4 x
    (16384 x 54016 + 2 x 128 x 4096 x 128 x 4) = 5.687e9 -> 6.94 ms
    (operations 4 x 3 x 16384 x 2752512 = 5.41e11 -> 2.75 ms, the
    smaller); attention operations 6 x 2 x 16384^2 x 4096 / 2 = 6.597e12
    -> 33.49 ms."""
    got = rehearsal.read_without_jax(READERS, {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "tokens_per_s": 40000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_grouped_matmul_fwd_": 0.02,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.01,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.01,
            "mosaic:ssm_scan_fwd": 0.15, "mosaic:ssm_scan_bwd": 0.25,
            "mosaic:flash_attention_fwd": 0.1,
            "mosaic:flash_attention_dq": 0.1,
            "mosaic:flash_attention_dkv": 0.2}}}, family="nemotron_h")
    assert got["expert_gmm_ms_per_step"] == pytest.approx(10.0)
    flops = 4 * 6 * 2 * 12288 * 2688 * 1856
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.01)
    assert got["ssm_scan_ms_per_step"] == pytest.approx(100.0)
    scan_bytes = 4 * (16384 * 54016 + 2 * 128 * 4096 * 128 * 4)
    assert got["ssm_scan_roofline"] == pytest.approx(
        100 * (scan_bytes / 819e9) / 0.1)
    assert got["ssm_scan_roofline"] == pytest.approx(6.94, abs=0.01)
    attn = 6 * 2 * 16384 ** 2 * 4096 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.1)
    from chipbench.families import nemotron_h
    assert got["mfu"] == pytest.approx(
        100 * nemotron_h.train_flops_per_token(load(CONFIG), 16384)
        * 40000.0 / 197e12)
