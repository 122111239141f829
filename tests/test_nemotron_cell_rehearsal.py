"""The `nemotron3nano-train-1chip` cell end to end at tiny size on the CPU,
through the benchmark's own command line (`chipbench/run.py --rehearsal`),
the tool its limits are read with, what BENCHMARK.json and the
configuration's file say of it, and the readers it shares with the OLMoE
and granite cells on a hand-made record at its real sizes.

The manifest is BENCHMARK.json as it is with the cell's configuration and
traffic mix swapped for new tiny stand-ins
(chipbench/tests/rehearsal/data/configs/nemotron-tiny.json,
.../traffic/tiny-train-nemotron.json: MEM*E, two groups, experts 2 to 5 of
8 held, chunks of 8, one sequence of 128), as tests/
test_olmoe_cell_rehearsal.py does for its cell and for its reason. The
numbers of a CPU run mean nothing and are written nowhere."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron3nano-train-1chip"
TINY = "chipbench/tests/rehearsal/data"


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory) -> str:
    m = _load("BENCHMARK.json")
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    m["paths"] = [TINY]
    config["file"] = f"{TINY}/configs/nemotron-tiny.json"
    cell["traffic"] = "tiny-train-nemotron"
    m["workloads"], m["configs"] = [cell], [config]
    path = tmp_path_factory.mktemp("nemotron_rehearsal") / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return str(path)


# tests/test_olmoe_cell_rehearsal.py has why run.py's one glob over
# /dev/shm answers nothing here.
RUN_PY = r"""
import glob, runpy, sys
_glob = glob.glob
glob.glob = lambda p, *a, **k: [] if str(p).startswith(
    "/dev/shm/ray_tpu_session_") else _glob(p, *a, **k)
sys.argv = ["chipbench/run.py"] + sys.argv[1:]
runpy.run_path("chipbench/run.py", run_name="__main__")
"""


def _env():
    return {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end_on_the_cpu(manifest_path, trace):
    proc = subprocess.run(
        [sys.executable, "-c", RUN_PY,
         "--rehearsal", manifest_path, "--workload", CELL, "--seed",
         "2147483900", "--seconds", "2.0", "--trace", str(trace)],
        capture_output=True, text=True, timeout=400, env=_env(), cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    detail, line = lines[-2], lines[-1]
    assert line["correct"] is True, (line, detail)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    check = detail["checks"]["loss_vs_reference"]
    assert abs(check["got"] - check["want"]) <= check["tolerance"]
    declared = {m["name"] for m in _load("BENCHMARK.json")[
        "per_layer" if trace else "end_to_end"]
        if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) <= declared
    if trace:
        assert {"step_ms_p50", "time_to_first_step_s"} <= set(
            line["metrics"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_limit_readings_reads_both_limits_and_every_planted_fault(
        manifest_path):
    """chipbench/limit_readings.py end to end at tiny size: a loss for the
    program, the reference, the all-bfloat16 reference and each planted
    fault, and the new layers' own errors for the same; KERNEL_LIMIT lies
    between the program and every planted fault."""
    from chipbench.families import nemotron_h as family

    proc = subprocess.run(
        [sys.executable, "chipbench/limit_readings.py", "--rehearsal",
         manifest_path, "--workload", CELL, "--seeds", "2147483900"],
        capture_output=True, text=True, timeout=900, env=_env(), cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    ranges = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(ranges["off_reference"]) == {"program", "all_bfloat16",
                                            *family.STRUCTURAL_FAULTS}
    worst = ranges["kernel_errors_worst"]
    assert set(worst) == set(ranges["off_reference"])
    assert ranges["kernel_limit"] == family.KERNEL_LIMIT
    assert worst["program"][1] <= family.KERNEL_LIMIT
    for name in family.STRUCTURAL_FAULTS:
        assert worst[name][0] > family.KERNEL_LIMIT, (name, worst[name])
    # at this size the all-bfloat16 forms lie above the program and about
    # the limit; the chip's reading at the published sizes is PERF.md's
    assert worst["all_bfloat16"][0] > worst["program"][1]


def test_step_counters_reads_the_held_rows_of_every_step(manifest_path):
    """chipbench/step_counters.py end to end at tiny size: the step at the
    default optimizer, its counters fetched a step; the held experts' rows
    stay near the balanced count the operations are reckoned for."""
    proc = subprocess.run(
        [sys.executable, "chipbench/step_counters.py", "--rehearsal",
         manifest_path, "--workload", CELL, "--seeds", "2147483900",
         "--steps", "6"],
        capture_output=True, text=True, timeout=900, env=_env(), cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    run = json.loads(proc.stdout.strip().splitlines()[-1])
    assert run["steps"] == 6 and run["rows_balanced"] > 0
    low, high = run["rows_held_over_balanced"]
    assert 0.8 < low <= high < 1.2, run
    assert run["loss_first_last"][1] < run["loss_first_last"][0]


def test_benchmark_lists_the_cell_under_the_metrics_issue_45_names():
    m = _load("BENCHMARK.json")
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
    on_disk = _load(config["file"])
    assert on_disk["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert on_disk["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    mix = _load("chipbench/traffic/pretrain-nemotron3nano-b1-s16384.json")
    assert (mix["global_batch"], mix["seq"], mix["remat"],
            mix["ring_batches"], mix["fetch_lag_groups"],
            mix["median_over_groups"]) == (1, 16384, True, 8, 1, 6)


def test_configuration_is_the_catalogs_but_the_four_keys_cut():
    """Every key of the catalog's entry at its value but depth, pattern,
    experts held and vocabulary; the published counts stated beside."""
    on_disk = _load("chipbench/configs/nemotron-3-nano-30b-a3b.json")
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
    """On a tree from before models/nemotron_h.py (the parent commit, with
    this benchmark laid over it) looking the cell up fails at once, in
    run.py's own process, before a cluster or a chip is touched."""
    import shutil
    tree = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "chipbench"), tree / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    shutil.copytree(os.path.join(ROOT, "ray_tpu"), tree / "ray_tpu",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", "nemotron_h.py", "*.so"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tree,
        capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode not in (0, 124, 137), proc.stderr[-2000:]
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
    code = r"""
import json, sys
sys.path.insert(0, %r)
from chipbench import harness
config = json.load(open("chipbench/configs/nemotron-3-nano-30b-a3b.json"))
from chipbench.families import nemotron_h
record = {
    "config": config,
    "counters": {"global_batch": 1, "seq": 16384, "chips": 1,
                 "tokens_per_s": 40000.0,
                 "train_flops_per_token":
                     nemotron_h.train_flops_per_token(config, 16384),
                 "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}},
    "trace": {"steps": 4, "mosaic_by_name": {
        "mosaic:jvp_grouped_matmul_fwd_": 0.02,
        "mosaic:transpose_jvp_grouped_matmul_dlhs__": 0.01,
        "mosaic:transpose_jvp_grouped_matmul_drhs__": 0.01,
        "mosaic:ssm_scan_fwd": 0.15, "mosaic:ssm_scan_bwd": 0.25,
        "mosaic:flash_attention_fwd": 0.1,
        "mosaic:flash_attention_dq": 0.1,
        "mosaic:flash_attention_dkv": 0.2}}}
out = {n: harness.reader(n).read(record) for n in %r}
assert "jax" not in sys.modules, "a reader imported jax"
print(json.dumps(out))
""" % (ROOT, READERS)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout)
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
    assert got["mfu"] == pytest.approx(
        100 * nemotron_flops() * 40000.0 / 197e12)


def nemotron_flops() -> float:
    from chipbench.families import nemotron_h
    return nemotron_h.train_flops_per_token(
        _load("chipbench/configs/nemotron-3-nano-30b-a3b.json"), 16384)
