"""The `ling3flash-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tools its limits and counters are read with, what BENCHMARK.json and
the traffic mix's file say of it, and its readers on a hand-made record at
its real sizes.

The tiny stand-ins are
chipbench/tests/rehearsal/data/configs/ling3flash-tiny.json and
.../traffic/tiny-train-ling3flash.json (a period of three: two KDA layers
of two heads of 32, which run the jax.numpy rule here, and a gated latent
layer; a dense layer, then experts 2 to 5 of 16 held in 4 groups of which
a token keeps 2; two sequences of 64); tests/cell_rehearsal.py has the
manifest, the runs and why the cell is rehearsed from here.
tests/test_bailing_hybrid.py holds the layers to the reference and plants
all fifteen faults in a layer's program; the pass here plants none."""

import json
import os

import pytest

import cell_rehearsal as rehearsal
from cell_rehearsal import load

CELL = "ling3flash-train-1chip"
CONFIG = "chipbench/configs/ling-3.0-flash.json"
MIX = "chipbench/traffic/pretrain-ling3flash-b1-s16384.json"
# chipbench/limit_readings.py with none of the family's fifteen faults
# planted: a fault's loss and kernel errors are a program of its own each,
# and tests/test_bailing_hybrid.py plants all fifteen in a layer's program.
KEPT_FAULTS = ()


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    return rehearsal.manifest(tmp_path_factory, CELL, "ling3flash-tiny",
                              "tiny-train-ling3flash")


def test_cell_runs_end_to_end_on_the_cpu(manifest_path):
    """The traced run: the loop on the ring, the comparison that decides
    `correct` (the loss against the reference's), the driver's falling-loss
    check, the trace's reduction and every reader the cell is listed
    under."""
    detail, _ = rehearsal.run_cell(manifest_path, CELL, 2147483900, 1)
    checks = detail["checks"]
    assert 6.0 < checks["loss_vs_reference"]["want"] < 6.5   # ln 512 = 6.24
    assert checks["last_loss"] < checks["first_loss"]
    assert checks["compiled_in_window"] == 0
    assert set(detail["end_to_end"]) == {"train_tokens_per_s"}


def test_limit_readings_reads_both_limits(manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference and the all-bfloat16 reference, and the layers'
    own errors for the same, every value of the four groups."""
    from chipbench.families import bailing_hybrid as family

    rows, ranges = rehearsal.limit_readings(manifest_path, CELL, 2147483900,
                                            family, KEPT_FAULTS)
    errors = rows[0]["kernel_errors"]
    assert set(errors["program"]) == {
        *(f"rule_{v}" for v in family._RULE_VALUES),
        *(f"kda_{v}" for v in ("out", "dx", *(
            "d" + n for n in family._KDA_NAMES))),
        *(f"mla_{v}" for v in ("out", "dx", *(
            "d" + n for n in family._MLA_NAMES))),
        "moe_out", "moe_dx", "moe_drouter", "moe_dgate_up", "moe_ddown",
        "moe_dshared_gate_up", "moe_dshared_down"}
    # The limit is the chip's, set at the published sizes (PERF.md section
    # 4): here the program is the jax.numpy forms in bfloat16 at a toy
    # size, which read some times the limit itself.
    held = family.held(errors["program"])
    assert max(held.values()) <= 8 * family.KERNEL_LIMIT
    assert ranges["off_reference"]["program"][1] <= ranges["tolerance"]


def test_step_counters_read_a_row_a_layer(manifest_path):
    """chipbench/step_counters.py at tiny size: `expert_rows_held` comes a
    row an expert layer, each read against the balanced count."""
    line = rehearsal.step_counters(manifest_path, CELL, 3, 3)
    # 128 tokens, 3 of 16 experts a token, 4 held: 96 rows a layer
    assert line["rows_balanced"] == 96
    low, high = line["rows_held_over_balanced"]
    assert 0.5 < low <= high < 1.5
    with open(os.path.join(rehearsal.ROOT, "chiprun_out",
                           f"step_counters_{CELL}.json")) as f:
        steps = json.load(f)[0]["per_step"]
    assert all(len(step["expert_rows_held"]) == 2 for step in steps)


def test_benchmark_lists_the_cell_under_the_metrics_issue_63_names():
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
        "expert_gmm_roofline", "kda_ms_per_step", "kda_roofline"}
    # every list glm47flash-train-1chip is on and its own two, no other
    order = [w["name"] for w in m["workloads"]]
    own = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in own] == ["kda_ms_per_step", "kda_roofline"]
    # appended by PR 63, and only appended after since
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
    cell = m["workloads"][11]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "ling-3.0-flash", "pretrain-ling3flash-b1-s16384", 1)
    assert all(len(x["why"]) <= 200 for x in (*m["workloads"], *m["configs"]))
    config = m["configs"][10]
    on_disk = load(config["file"])
    assert config["file"] == CONFIG
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert on_disk["source"] == config["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
        "config.json")
    assert set(on_disk["reduced_from"]) == set(on_disk["reduced"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = load(MIX)
    assert (mix["driver"], mix["global_batch"], mix["seq"], mix["mesh_dp"],
            mix["remat"], mix["ring_batches"], mix["report_every"],
            mix["fetch_lag_groups"], mix["median_over_groups"],
            mix["warmup_steps"], mix["traced_steps"],
            mix["reference_sample_sequences"]) == (
        "train", 1, 16384, 0, True, 8, 2, 1, 6, 3, 4, 1)
    assert "TO BE FILLED" not in json.dumps(mix)
    from chipbench.families import bailing_hybrid as family
    rate = {1e-4: "1e-4", 1e-5: "1e-5", 1e-6: "1e-6"}[family.LEARNING_RATE]
    assert f"constant {rate}" in mix["optimizer"]
    assert f"constant {rate}" in on_disk["assumed"]["optimizer"]


def test_family_refuses_a_tree_without_the_program(tmp_path):
    """On a tree from before models/bailing_hybrid.py (the parent commit,
    with this benchmark laid over it) looking the cell up fails at once."""
    proc = rehearsal.lookup_in_tree_without(
        tmp_path, CELL, ("bailing_hybrid.py", "kda.py"),
        "from .bailing_hybrid import")
    assert "cannot run a bailing_hybrid configuration" in proc.stderr


READERS = ("kda_ms_per_step", "kda_roofline", "attn_scoped_roofline",
           "expert_gmm_roofline", "mfu")


def test_readers_give_the_hand_computed_numbers_and_import_no_jax():
    """A hand-made record at the cell's real sizes: 4 traced steps, the KDA
    kernels 0.48 s, attention's three 0.4 s, the grouped matmuls 0.04 s,
    17,000 tokens a second. By hand: the rule's operations 5 layers x 3 x
    16,384 x 4,476,928 a token = 1.100e12 -> 5.6 ms at 197 TFLOP/s; its
    bytes 5 x (16,384 x 98,560 + 1,073,741,824) = 1.344e10 -> 16.4 ms at
    819 GB/s, the larger: 13.7% of 120 ms. Rows of other scopes are not the
    rule's, whatever they share of its name."""
    from chipbench.families import bailing_hybrid as family

    record = {
        "config": load(CONFIG),
        "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                     "tokens_per_s": 17000.0,
                     "peaks": {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9}},
        "trace": {"steps": 4, "mosaic_by_name": {
            "mosaic:jvp_kda_fwd_": 0.2,
            "mosaic:transpose_jvp_kda_bwd__": 0.28,
            "mosaic:gated_delta_fwd": 7.0,
            "mosaic:jvp_grouped_matmul_fwd_": 0.02,
            "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.01,
            "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.01,
            "mosaic:flash_attention_fwd": 0.1,
            "mosaic:flash_attention_dq": 0.1,
            "mosaic:flash_attention_dkv": 0.2}}}
    got = rehearsal.read_without_jax(READERS, record,
                                     family="bailing_hybrid")
    assert family.KDA_KERNEL_ROWS == ("kda_fwd", "kda_bwd")
    assert got["kda_ms_per_step"] == pytest.approx(120.0)
    nbytes = family.kda_bytes(load(CONFIG), 1, 16384)
    assert nbytes == 5 * (16384 * 98_560 + 1_073_741_824)
    assert nbytes / 819e9 > family.kda_flops(load(CONFIG), 1, 16384) / 197e12
    assert got["kda_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.12)
    assert got["kda_roofline"] == pytest.approx(13.7, abs=0.05)
    assert family.attention_kernel_flops(load(CONFIG), 1, 16384) \
        == 2 * 16384 ** 2 * 32 * 3 * 320 / 2
    assert got["attn_scoped_roofline"] == pytest.approx(
        100 * (2 * 16384 ** 2 * 32 * 3 * 320 / 2 / 197e12) / 0.1)
    # the experts' bytes bind at a thirty-second of a deployment's rows
    assert family.expert_matmul_flops(load(CONFIG), 16384) \
        == 5 * 9 * 2 * 4096 * 2560 * 768
    gmm_bytes = 5 * 9 * 2 * (4096 * (2560 + 768) + 16 * 2560 * 768)
    assert family.expert_matmul_bytes(load(CONFIG), 16384) == gmm_bytes
    assert got["expert_gmm_roofline"] == pytest.approx(
        100 * (gmm_bytes / 819e9) / 0.01)
    assert got["mfu"] == pytest.approx(
        100 * family.train_flops_per_token(load(CONFIG), 16384)
        * 17000.0 / 197e12)
    assert got["mfu"] == pytest.approx(27.5, abs=0.1)
    assert all(0 < got[name] <= 100 for name in READERS[1:])
    # on a record with no such row (another program's trace) and under a
    # family that names no such rows the two new readers read nothing and
    # do not raise
    record["trace"]["mosaic_by_name"] = {"mosaic:flash_attention_fwd": 0.3,
                                         "mosaic:gated_delta_fwd": 7.0}
    bare = rehearsal.read_without_jax(READERS[:2], record)
    assert bare == {"kda_ms_per_step": None, "kda_roofline": None}
    record["trace"]["mosaic_by_name"]["mosaic:kda_fwd"] = 0.2
    record["config"] = load("chipbench/configs/gpt2-small.json")
    assert rehearsal.read_without_jax(READERS[:2], record) == bare
