"""The `lfm2moe-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tools its limits and counters are read with, what BENCHMARK.json and
the configuration's file say of it, and its readers on a hand-made record
at its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/lfm2moe-tiny.json and
.../traffic/tiny-train-lfm2moe.json (one dense layer then three expert
layers, conv | full_attention conv conv, experts 2 to 5 of 8 held, two
sequences of 64); tests/cell_rehearsal.py has the manifest, the runs and
why the cell is rehearsed from here."""

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "lfm2moe-train-1chip"
CONFIG = "chipbench/configs/lfm2-8b-a1b.json"
# chipbench/limit_readings.py with two of the family's eight faults to
# plant, one of the convolution's and one of the held experts': the pass
# reads each fault's loss and kernel errors in a program of its own. All
# eight are planted in-process, on the model's loss and on the layers
# (tests/test_lfm2_moe.py::
# test_a_planted_fault_moves_the_loss_and_its_layer[*]).
KEPT_FAULTS = ("rows_read_across_sequences", "silu_on_the_wrong_half")


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "lfm2moe-tiny",
                              "tiny-train-lfm2moe")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 2147483900, trace)


def test_limit_readings_reads_both_limits_and_a_fault_of_each_kind(
        manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and a planted fault
    of the convolution and of the held experts (KEPT_FAULTS), and the new
    layers' own errors for the same; KERNEL_LIMIT lies between the program
    and every planted fault."""
    from chipbench.families import lfm2_moe as family

    assert set(family.STRUCTURAL_FAULTS) == {
        "tap_read_one_row_ahead", "rows_read_across_sequences",
        "b_gate_dropped", "c_gate_dropped", "silu_on_the_wrong_half",
        "absent_rows_computed", "bias_added_to_the_weights",
        "norm_over_all_columns"}
    _, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                         family, KEPT_FAULTS)
    worst = ranges["kernel_errors_worst"]
    # The limit is the chip's, set between the kernels' reading and the
    # all-bfloat16 forms' at the published sizes (PERF.md section 4): here
    # the program is the jax.numpy forms in bfloat16 at a toy size, which
    # read about the limit itself (0.009, the attention's gradient by its
    # rows), under the all-bfloat16 forms and far under every fault.
    assert worst["program"][1] <= 1.5 * family.KERNEL_LIMIT
    assert worst["all_bfloat16"][0] > worst["program"][1]
    for name in KEPT_FAULTS:
        assert worst[name][0] > 20 * family.KERNEL_LIMIT, (name, worst[name])


def test_step_counters_reads_the_held_rows_of_every_step(manifest_path):
    """chipbench/step_counters.py end to end at tiny size: the step at the
    default optimizer, its counters fetched a step; the held experts' rows
    stay near the balanced count the operations are reckoned for."""
    run = rehearsal.step_counters(manifest_path, CELL, 2147483900, 6)
    assert run["steps"] == 6 and run["rows_balanced"] == 2 * 64 * 3 * 4 / 8
    low, high = run["rows_held_over_balanced"]
    assert 0.8 < low <= high < 1.2, run
    assert run["loss_first_last"][1] < run["loss_first_last"][0]


def test_benchmark_lists_the_cell_under_the_metrics_issue_48_names():
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
        "attn_scoped_roofline", "expert_gmm_ms_per_step",
        "expert_gmm_roofline", "short_conv_ms_per_step",
        "short_conv_roofline"}
    order = [w["name"] for w in m["workloads"]]
    for x in (*m["end_to_end"], *m["per_layer"]):
        if CELL in x.get("workloads", ()):
            # appended, nothing moved: every list in the cells' own order
            assert x["workloads"] == [n for n in order
                                      if n in x["workloads"]], x["name"]
    new = [x for x in m["per_layer"] if x["name"].startswith("short_conv_")]
    assert [x["name"] for x in new] == ["short_conv_ms_per_step",
                                        "short_conv_roofline"]
    for x in new:
        assert (x["source"], x["layer"], x["moves"], x["workloads"]) == (
            "device_trace", "kernels", "train_tokens_per_s", [CELL])
    cell = m["workloads"][7]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "lfm2-8b-a1b", "pretrain-lfm2moe-s8192", 1)
    assert len(m["workloads"]) >= 8 and len(m["configs"]) >= 7
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][6]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load("chipbench/traffic/pretrain-lfm2moe-s8192.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"],
            mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        4, 8192, True, 8, 3, 1, 6, 3, 4, 1)


def test_configuration_is_the_catalogs_but_the_five_keys_cut():
    """Every key of the catalog's entry at its value but depth, the layer
    types, the dense layers, the experts held and the vocabulary; the
    published counts stated beside."""
    on_disk = load(CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: on_disk[k] for k in published} == published
    cut = {"num_hidden_layers": 5,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
           "num_dense_layers": 1, "num_experts": 16, "vocab_size": 32768}
    assert {k: on_disk[k] for k in cut} == cut
    assert set(on_disk["reduced_from"]) == set(cut) == set(on_disk["reduced"])
    # layer 0 and layers 2 to 5 of the published order
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig
    full = Lfm2MoeConfig.lfm2_8b_a1b().layer_types
    assert on_disk["layer_types"] == [full[0], *full[2:6]]
    assert on_disk["deployment_sizes"] == {
        "chips_sharing_a_layer": 2, "num_experts": 32,
        "first_expert_held": 0, "vocab_size": 65536,
        "num_hidden_layers": 24}
    assumed = on_disk["assumed"]
    assert (assumed["head_dim"], assumed["tie_word_embeddings"],
            assumed["topk_weight_eps"], assumed["balance_tokens"]) == (
        64, True, 1e-6, 32768)
    assert assumed["bias_rounds"] % 8 == 0
    assert any("expert_bias is not zero at the start" in line
               for line in on_disk["departures"])
    for key in ("assumed", "departures", "deployment"):
        assert on_disk[key], key
    # the floors of a model_config cut: the dense layer once and a whole
    # period of four layers after it, 16 >= 8 experts, half >= an eighth
    # of the vocabulary
    assert on_disk["layer_types"][1:].count("full_attention") == 1
    assert on_disk["vocab_size"] * 2 == 65536


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/lfm2_moe.py (the parent commit, with this
    benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("lfm2_moe.py",))
    assert "cannot run a lfm2-moe configuration" in proc.stderr


READERS = ("expert_gmm_ms_per_step", "expert_gmm_roofline",
           "short_conv_ms_per_step", "short_conv_roofline",
           "attn_scoped_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps,
    grouped matmuls 0.4 s, the convolution's kernels 0.08 s, attention 0.2
    s, 55,000 tokens a second. By hand, for a balanced share (65,536 rows
    a layer, 4 layers): expert operations 4 x 9 x 2 x 65536 x 2048 x 1792
    = 1.7317e13 -> 87.9 ms at 197 TFLOP/s (bytes 4 x 9 x 2 x (65536 x 3840
    + 16 x 2048 x 1792) = 2.235e10 -> 27.3 ms at 819 GB/s, the smaller);
    the convolution's bytes 4 x 32768 x 2048 x 11 x 2 = 5.906e9 -> 7.21 ms
    (operations 4 x 32768 x 2048 x 30 = 8.05e9 -> 0.04 ms, the smaller);
    attention operations 6 x 2 x 4 x 8192^2 x 2048 / 2 = 3.2985e12 ->
    16.74 ms."""
    got = rehearsal.read_without_jax(READERS, {
        "config": load(CONFIG),
        "counters": {"global_batch": 4, "seq": 8192, "chips": 1,
                     "tokens_per_s": 55000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_grouped_matmul_fwd_": 0.2,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.1,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.1,
            "mosaic:jvp_short_conv_fwd_": 0.03,
            "mosaic:transpose_jvp_short_conv_bwd__": 0.05,
            "mosaic:flash_attention_fwd": 0.05,
            "mosaic:flash_attention_dq": 0.05,
            "mosaic:flash_attention_dkv": 0.1}}}, family="lfm2_moe")
    assert got["expert_gmm_ms_per_step"] == pytest.approx(100.0)
    flops = 4 * 9 * 2 * 65536 * 2048 * 1792
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (flops / 197e12) / 0.1)
    assert got["expert_gmm_roofline"] == pytest.approx(87.9, abs=0.05)
    assert got["short_conv_ms_per_step"] == pytest.approx(20.0)
    conv_bytes = 4 * 32768 * 2048 * 11 * 2
    assert got["short_conv_roofline"] == pytest.approx(
        100 * (conv_bytes / 819e9) / 0.02)
    assert got["short_conv_roofline"] == pytest.approx(36.05, abs=0.01)
    attn = 6 * 2 * 4 * 8192 ** 2 * 2048 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.05)
    from chipbench.families import lfm2_moe
    assert got["mfu"] == pytest.approx(
        100 * lfm2_moe.train_flops_per_token(load(CONFIG), 8192)
        * 55000.0 / 197e12)


def test_a_record_without_the_convolutions_rows_leaves_the_metrics_out():
    """What the parent gives for a metric new in this PR: a trace with no
    `short_conv` row reads as nothing, and nothing is raised."""
    from chipbench import harness
    record = {"config": load(CONFIG),
              "counters": {"global_batch": 4, "seq": 8192, "chips": 1,
                           "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9}},
              "trace": {"steps": 4, "mosaic_by_name": {
                  "mosaic:flash_attention_fwd": 0.05}}}
    for name in ("short_conv_ms_per_step", "short_conv_roofline"):
        assert harness.reader(name).read(record) is None
        assert harness.reader(name).read({"counters": {}}) is None
