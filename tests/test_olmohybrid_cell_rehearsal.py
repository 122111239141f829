"""The `olmohybrid-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
and its two new per-layer readers on hand-made records.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/olmohybrid-tiny.json and
.../traffic/tiny-train-olmohybrid.json (one period of three
linear-attention layers and a full-attention layer, 3 heads of 12 x 20 in
chunks of 8, one sequence of 128); tests/cell_rehearsal.py has the
manifest, the runs and why the cell is rehearsed from here."""

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "olmohybrid-train-1chip"


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "olmohybrid-tiny",
                              "tiny-train-olmohybrid")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 2147483900, trace)


def test_limit_readings_reads_both_limits_and_every_planted_fault(
        manifest_path):
    """chipbench/limit_readings.py, the tool the cell's two limits were
    set with on the chip, end to end at tiny size: a loss for the program,
    the reference, the all-bfloat16 reference and each planted fault, and
    the kernels' own errors for the same. The kernels' limit lies between
    the program's reading and every other, each of the five structural
    faults' among them."""
    from chipbench.families import olmo_hybrid as family

    faults = set(family.STRUCTURAL_FAULTS)
    assert faults == {"correction_dropped", "beta_not_doubled",
                      "decay_dropped", "chunk_carry_dropped",
                      "l2_norm_dropped"}
    rows, _ = rehearsal.limit_readings(manifest_path, CELL, "3,2147483900",
                                       family)
    # what the family holds to that limit is the mean of a reading's eight
    # errors (`held`), seed by seed (the tool's summary takes the largest)
    rows = [r["kernel_errors"] for r in rows]
    assert len(rows) == 2 and all(len(r["program"]) == 8 for r in rows)
    for errors in rows:
        assert family.held(errors["program"]) <= family.KERNEL_LIMIT
        for name in ("all_bfloat16", *faults):
            assert not family.held(errors[name]) <= family.KERNEL_LIMIT, (
                name, errors[name])


def test_benchmark_lists_the_cell_under_the_metrics_issue_41_names():
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
        "attn_scoped_roofline", "gated_delta_ms_per_step",
        "gated_delta_roofline"}
    # the other families' scan readers match their scopes as substrings:
    # not this cell's
    for name in ("ssm_scan_ms_per_step", "ssm_scan_roofline",
                 "selective_scan_ms_per_step", "selective_scan_roofline",
                 "attn_kernel_ms_per_step", "flash_attention_roofline"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert CELL not in metric["workloads"]
    for name in READERS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert (metric["layer"], metric["source"], metric["moves"],
                metric["workloads"]) == (
                    "kernels", "device_trace", "train_tokens_per_s", [CELL])
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "pretrain-olmohybrid-b1-s16384", 1)
    assert m["workloads"][5] is cell and len(m["workloads"]) >= 6
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert m["configs"][4] is config and len(config["why"]) <= 200
    on_disk = load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load("chipbench/traffic/pretrain-olmohybrid-b1-s16384.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"], mix["mesh_dp"],
            mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
                1, 16384, True, 0, 8, 2, 1, 6, 3, 4, 1)


def test_configuration_is_the_catalog_entry_but_depth_and_vocabulary():
    """Every number of the catalog's entry at its value but the three that
    `reduced` names, and every line of the layer equations that
    config.json does not give under `assumed`."""
    on_disk = load("chipbench/configs/olmo-hybrid-7b.json")
    period = ["linear_attention"] * 3 + ["full_attention"]
    catalog = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    differs = {k for k, v in catalog.items() if on_disk[k] != v}
    assert differs == set(on_disk["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert (on_disk["num_hidden_layers"], on_disk["layer_types"],
            on_disk["vocab_size"]) == (4, period, 25088)
    assert on_disk["vocab_size"] * 4 == catalog["vocab_size"]
    assert on_disk["vocab_size"] % 128 == 0
    assert set(on_disk["reduced_from"]) == set(on_disk["reduced"])
    assert {"head_dim", "rope", "linear_block", "full_block",
            "full_attention", "linear_attention", "mlp", "init",
            "dtype"} <= set(on_disk["assumed"])
    assert on_disk["assumed"]["head_dim"] == 128
    for key in ("departures", "deployment"):
        assert on_disk[key], key


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/olmo_hybrid.py (the parent commit, with
    this benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("olmo_hybrid.py", "gated_delta.py"))
    assert "cannot run an olmo_hybrid configuration" in proc.stderr
    assert proc.stdout.strip() == ""


READERS = ("gated_delta_ms_per_step", "gated_delta_roofline")


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_on_an_empty_record(name):
    from chipbench import harness

    empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
    assert harness.reader(name).read(empty) is None
    # a program with the other families' kernels only (the parent) has no
    # such row
    others = {"counters": {"chips": 1}, "seconds": 1.0, "trace": {
        "steps": 4, "mosaic_by_name": {"mosaic:flash_attention_fwd": 0.1,
                                       "mosaic:ssm_scan_fwd": 0.1,
                                       "mosaic:selective_scan_bwd": 0.1}}}
    assert harness.reader(name).read(others) is None


def test_other_readers_do_not_read_the_delta_rule_rows():
    from chipbench import harness

    mine = {"counters": {"chips": 1}, "seconds": 1.0, "trace": {
        "steps": 4, "mosaic_by_name": {
            "mosaic:gated_delta_fwd": 0.1,
            "mosaic:transpose_jvp_gated_delta_bwd__": 0.2}}}
    for other in ("ssm_scan_ms_per_step", "selective_scan_ms_per_step",
                  "expert_gmm_ms_per_step", "attn_fwd_kernel_ms_per_step",
                  "attn_dq_kernel_ms_per_step",
                  "attn_dkv_kernel_ms_per_step"):
        assert harness.reader(other).read(mine) is None, other
    assert harness.reader("gated_delta_ms_per_step").read(
        mine) == pytest.approx(75.0)


@pytest.mark.parametrize("seq,batch", [(16384, 1), (4096, 2)])
def test_readers_give_the_hand_computed_numbers_and_import_no_jax(seq,
                                                                  batch):
    """A hand-made mosaic_by_name at the cell's real widths: 4 traced
    steps, the delta-rule kernels 0.1 + 0.2 s. By hand, three
    linear-attention layers of 30 heads x 96 x 192: operations 3 layers x
    tokens x 30 x 18 x 96 x 192; bytes 3 x tokens x (4 x 2880 x 2 + 4 x
    5760 x 2 + 4 x 30 x 4) = 3 x tokens x 69,600, the larger at the chip's
    peaks (4.18 ms against 2.49 at 16,384 tokens): 4.18 / 75 ms = 5.57%."""
    got = rehearsal.read_without_jax(READERS + ("attn_scoped_roofline",), {
        "config": load("chipbench/configs/olmo-hybrid-7b.json"),
        "counters": {"global_batch": batch, "seq": seq, "chips": 1,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:gated_delta_fwd": 0.1,
            "mosaic:transpose_jvp_gated_delta_bwd__": 0.2,
            "mosaic:flash_attention_fwd": 0.06,
            "mosaic:flash_attention_dq": 0.07,
            "mosaic:flash_attention_dkv": 0.12}}})
    assert got["gated_delta_ms_per_step"] == pytest.approx(75.0)
    tokens = batch * seq
    nbytes = 3 * tokens * (4 * 2880 * 2 + 4 * 5760 * 2 + 4 * 30 * 4)
    flops = 3 * tokens * 30 * 18 * 96 * 192
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound applies
    assert got["gated_delta_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.075)
    if tokens == 16384:
        assert got["gated_delta_roofline"] == pytest.approx(5.57, abs=0.01)
    attn = 30 * 2 * 6 * 128 * batch * seq * seq / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.0625)
