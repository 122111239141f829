"""The `phi4flash-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
and its two new per-layer readers on hand-made records.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/phi4flash-tiny.json and
.../traffic/tiny-train-phi4flash.json (eight layers by the model's own
rule, 128 Mamba-1 channels x 4 states, a window of 32, one sequence of
128); tests/cell_rehearsal.py has the manifest, the runs and why the cell
is rehearsed from here."""

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "phi4flash-train-1chip"


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "phi4flash-tiny",
                              "tiny-train-phi4flash")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 2147483900, trace)


def test_limit_readings_reads_both_limits_and_every_planted_fault(
        manifest_path):
    """chipbench/limit_readings.py, the tool the cell's two limits were
    set with on the chip, end to end at tiny size: a loss for the program,
    the reference, the all-bfloat16 reference and each planted fault, and
    the kernels' own errors for the same. The kernels' limit lies between
    the program and everything else, each of the four structural faults
    among them."""
    from chipbench.families import sambay as family

    faults = set(family.STRUCTURAL_FAULTS)
    assert faults == {"window_ignored", "lambda_term_dropped",
                      "chunk_carry_dropped", "gmu_memory_zeroed"}
    _, ranges = rehearsal.limit_readings(manifest_path, CELL,
                                         "3,2147483900", family)
    worst = ranges["kernel_errors_worst"]
    assert worst["program"][1] <= family.KERNEL_LIMIT
    for name in ("all_bfloat16", *faults):
        assert worst[name][0] > family.KERNEL_LIMIT, (name, worst[name])


def test_benchmark_lists_the_cell_under_the_metrics_issue_31_names():
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
        "attn_scoped_roofline", "selective_scan_ms_per_step",
        "selective_scan_roofline"}
    # Mamba-2's scan readers match `ssm_scan` as a substring: not this cell's
    for name in ("ssm_scan_ms_per_step", "ssm_scan_roofline",
                 "attn_kernel_ms_per_step", "flash_attention_roofline"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert CELL not in metric["workloads"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "pretrain-phi4flash-b1-s16384", 1)
    assert m["workloads"][4] is cell and len(m["workloads"]) >= 5
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert m["configs"][3] is config and len(config["why"]) <= 200
    on_disk = load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load("chipbench/traffic/pretrain-phi4flash-b1-s16384.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"], mix["mesh_dp"],
            mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["reference_sample_sequences"]) == (
                1, 16384, True, 0, 8, 2, 1, 6, 1)


def test_configuration_is_the_catalog_entry_but_depth_and_vocabulary():
    """Every number of the catalog's entry at its value but the two that
    `reduced` names, and every line of the layer equations that
    config.json does not give under `assumed`."""
    on_disk = load("chipbench/configs/phi-4-mini-flash-reasoning.json")
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    differs = {k for k, v in catalog.items() if on_disk[k] != v}
    assert differs == set(on_disk["reduced"]) == {"num_hidden_layers",
                                                  "vocab_size"}
    assert (on_disk["num_hidden_layers"], on_disk["vocab_size"]) == (
        8, 50016)
    assert on_disk["vocab_size"] * 4 == catalog["vocab_size"]
    assert set(on_disk["reduced_from"]) == set(on_disk["reduced"])
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_dt_rank", "block", "mlp", "mamba1",
            "differential_attention", "windows", "gmu", "cross_attention",
            "init", "dtype"} <= set(on_disk["assumed"])
    for key in ("departures", "deployment"):
        assert on_disk[key], key


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/sambay.py (the parent commit, with this
    benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("sambay.py", "selective_scan.py"))
    assert "cannot run a sambay configuration" in proc.stderr
    assert proc.stdout.strip() == ""


READERS = ("selective_scan_ms_per_step", "selective_scan_roofline")


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_on_an_empty_record(name):
    from chipbench import harness

    empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
    assert harness.reader(name).read(empty) is None
    # a program with Mamba-2's scan and attention kernels only (the
    # parent) has no such row
    others = {"counters": {"chips": 1}, "seconds": 1.0, "trace": {
        "steps": 4, "mosaic_by_name": {"mosaic:flash_attention_fwd": 0.1,
                                       "mosaic:ssm_scan_fwd": 0.1}}}
    assert harness.reader(name).read(others) is None


def test_ssm_scan_readers_do_not_read_the_selective_scan_rows():
    from chipbench import harness

    mine = {"counters": {"chips": 1}, "seconds": 1.0, "trace": {
        "steps": 4, "mosaic_by_name": {
            "mosaic:selective_scan_fwd": 0.1,
            "mosaic:transpose_jvp_selective_scan_bwd__": 0.2}}}
    assert harness.reader("ssm_scan_ms_per_step").read(mine) is None
    assert harness.reader("selective_scan_ms_per_step").read(
        mine) == pytest.approx(75.0)


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made mosaic_by_name at the cell's real sizes: 4 traced
    steps, the scan kernels 0.04 + 0.1 s. By hand, three Mamba-1 layers of
    16,384 tokens x 5,120 channels x 16 states: operations 3 x 3 x 6 x
    16384 x 5120 x 16 = 7.248e10 -> 0.37 ms at 197 TFLOP/s; bytes 3 x
    16384 x (5120 x 22 + 6 x 16 x 2) = 5.546e9 -> 6.77 ms at 819 GB/s, the
    larger: 6.77 / 35 ms = 19.35%. The attention kernels: two windowed
    layers over the band and two over the triangle."""
    got = rehearsal.read_without_jax(READERS + ("attn_scoped_roofline",), {
        "config": load("chipbench/configs/phi-4-mini-flash-reasoning.json"),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:selective_scan_fwd": 0.04,
            "mosaic:transpose_jvp_selective_scan_bwd__": 0.1,
            "mosaic:flash_attention_fwd": 0.06,
            "mosaic:flash_attention_dq": 0.07,
            "mosaic:flash_attention_dkv": 0.12}}})
    assert got["selective_scan_ms_per_step"] == pytest.approx(35.0)
    nbytes = 3 * 16384 * (5120 * 22 + 6 * 16 * 2)
    flops = 3 * 3 * 6 * 16384 * 5120 * 16
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound applies
    assert got["selective_scan_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.035)
    assert got["selective_scan_roofline"] == pytest.approx(19.35, abs=0.01)
    S, w = 16384, 512
    band = w * (w + 1) // 2 + (S - w) * w
    triangle = S * (S + 1) // 2
    attn = 40 * 2 * 9 * 64 * (2 * band + 2 * triangle)
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.0625)
