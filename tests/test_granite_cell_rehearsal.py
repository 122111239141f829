"""The `granite4h-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
and its two new per-layer readers on hand-made records.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/granite-tiny.json and
.../traffic/tiny-train-granite.json (two Mamba-2 layers and one attention
layer, chunks of 8, one sequence of 128); tests/cell_rehearsal.py has the
manifest, the runs and why the cell is rehearsed from here."""

import os

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "granite4h-train-1chip"


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "granite-tiny",
                              "tiny-train-granite")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    rehearsal.run_cell(manifest_path, CELL, 3, trace)


def test_limit_readings_reads_both_limits_and_every_planted_fault(
        manifest_path):
    """chipbench/limit_readings.py, the tool the cell's two limits were
    set with on the chip, end to end at tiny size: a loss for the program,
    the reference, the all-bfloat16 reference and each planted fault, and
    the scan's own errors for the same. At this size the loss tells
    nothing apart (at the timed size it sees only a scan whose state never
    reaches the output, PERF.md section 4); the scan's own limit lies
    between the program and everything else but the one fault of
    precision that the kernels' bfloat16 products already hide."""
    from chipbench.families import granite_hybrid as family

    _, ranges = rehearsal.limit_readings(manifest_path, CELL,
                                         "3,2147483900", family)
    worst = ranges["kernel_errors_worst"]
    assert worst["program"][1] <= family.KERNEL_LIMIT
    for name in ("all_bfloat16", *family.STRUCTURAL_FAULTS):
        assert worst[name][0] > family.KERNEL_LIMIT, (name, worst[name])


def test_scope_profile_builds_the_step_and_refuses_without_a_chip(
        manifest_path):
    """chipbench/scope_profile.py, the tool PERF.md section 5's tables
    come from, end to end at tiny size: it builds the cell's step from the
    family's train program, warms it up and captures a step, and then,
    since the CPU's trace holds no device plane, says so, exits 3 and
    writes nothing: no table of a step's device time comes from a run
    that had no device."""
    written = os.path.join(rehearsal.ROOT, "chiprun_out",
                           f"scope_profile_{CELL}.json")
    before = os.stat(written).st_mtime_ns if os.path.exists(written) else None
    proc = rehearsal.scope_profile(manifest_path, CELL, 3, 1)
    assert proc.returncode == 3, proc.stderr[-3000:]
    said = proc.stderr.strip().splitlines()[-1]
    assert said.startswith(f"scope_profile: {CELL}: 1 step(s) ran on cpu "
                           "(loss ") and said.endswith(
        "holds 0 device plane(s): no device time to read, nothing written")
    assert proc.stdout == ""
    after = os.stat(written).st_mtime_ns if os.path.exists(written) else None
    assert after == before


def test_benchmark_lists_the_cell_under_the_metrics_issue_29_names():
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
        "attn_scoped_roofline", "ssm_scan_ms_per_step", "ssm_scan_roofline"}
    # the two that divide by the time of ALL Mosaic kernels do not list it
    for name in ("attn_kernel_ms_per_step", "flash_attention_roofline"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        assert CELL not in metric["workloads"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "pretrain-granite4h-b1-s16384", 1)
    assert m["workloads"][3] is cell and len(m["workloads"]) >= 4
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    on_disk = load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load("chipbench/traffic/pretrain-granite4h-b1-s16384.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"],
            mix["report_every"]) == (1, 16384, True, 2)


def test_configuration_is_one_whole_period_at_published_widths():
    """Every number of the catalog's entry at its value but the depth:
    the first ten of the forty published layer types, in order."""
    on_disk = load("chipbench/configs/granite-4.0-h-micro.json")
    assert on_disk["layer_types"] == ["mamba"] * 5 + ["attention"] + [
        "mamba"] * 4
    assert on_disk["num_hidden_layers"] == len(on_disk["layer_types"]) == 10
    published = {
        "hidden_size": 2048, "shared_intermediate_size": 8192,
        "intermediate_size": 8192, "num_attention_heads": 32,
        "num_key_value_heads": 8, "vocab_size": 100352,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "mamba_expand": 2, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 8, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 131072, "position_embedding_type": "nope",
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "tie_word_embeddings": True, "rope_theta": 10000}
    assert {k: on_disk[k] for k in published} == published
    for key in ("assumed", "departures", "deployment", "reduced_from"):
        assert on_disk[key], key


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/hybrid.py (the parent commit, with
    this benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("hybrid.py",))
    assert "cannot run a granite-hybrid configuration" in proc.stderr
    assert proc.stdout.strip() == ""


READERS = ("ssm_scan_ms_per_step", "ssm_scan_roofline")


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_on_an_empty_record(name):
    from chipbench import harness

    empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
    assert harness.reader(name).read(empty) is None
    # a program with attention kernels only (the parent) has no such row
    others = {"counters": {"chips": 1}, "seconds": 1.0, "trace": {
        "steps": 4, "mosaic_by_name": {"mosaic:flash_attention_fwd": 0.1}}}
    assert harness.reader(name).read(others) is None


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made mosaic_by_name at the cell's real sizes: 4 traced
    steps, the scan kernels 0.04 + 0.1 s. By hand, nine Mamba-2 layers of
    16,384 tokens: operations 9 x 3 x 16384 x (256 x 128 + 256 x 4096 + 4 x
    4096 x 128) = 1.4061e12 -> 7.14 ms at 197 TFLOP/s; bytes 9 x (16384 x
    (17152 + 26112) + 2 x 64 x 4096 x 128 x 4) = 8.7952e9 -> 10.74 ms at 819
    GB/s, the larger: 10.74 / 35 ms = 30.68%."""
    got = rehearsal.read_without_jax(READERS + ("attn_scoped_roofline",), {
        "config": load("chipbench/configs/granite-4.0-h-micro.json"),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:ssm_scan_fwd": 0.04,
            "mosaic:transpose_jvp_ssm_scan_bwd__": 0.1,
            "mosaic:flash_attention_fwd": 0.06,
            "mosaic:flash_attention_dq": 0.07,
            "mosaic:flash_attention_dkv": 0.12}}})
    assert got["ssm_scan_ms_per_step"] == pytest.approx(35.0)
    nbytes = 9 * (16384 * (17152 + 26112) + 2 * 64 * 4096 * 128 * 4)
    flops = 9 * 3 * 16384 * (256 * 128 + 256 * 4096 + 4 * 4096 * 128)
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound applies
    assert got["ssm_scan_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.035)
    assert got["ssm_scan_roofline"] == pytest.approx(30.68, abs=0.01)
    attn = 6 * 2 * 16384 ** 2 * 2048 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (attn / 197e12) / 0.0625)
