"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `trinity-train-1chip` cell as the cell runs it —
Trinity-Large-Preview at its published widths (d 3072; 48 query heads over 8
key-value heads of 128 under a gate a channel and four norms a block; layers
windowed (4,096, rotated), windowed, windowed, full (no positions),
windowed; a dense SwiGLU of 12,288, then a 256-wide sigmoid router over 8
held SwiGLU experts of 3,072 and a shared one; V 25,024 untied), five layers,
B=1 x S=8192, remat on, AdamW at the family's rate — compiles for one chip
with 12.86 GB of state, calls exactly the attention and grouped-matmul
kernels under the program's scopes, the four windowed layers' under names of
their own, each attention kernel once a layer though remat is on, and fits
the chip by XLA's memory analysis a GiB under its capacity, with
`remat_plan`'s account beside it (PERF.md section 4 has the figures). The
XLA compile is about a minute of one worker: ONE compile, which every case
of the compiled step reads. tests/compile_v5e.py has the described topology
and the lowering."""

import pytest

from chipbench.families import afmoe as family
from compile_v5e import (HBM_BYTES, lowered_cell_step,  # noqa: F401
                         mosaic_call_types, topo, total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, family, "configs/trinity-large-preview.json",
        "traffic/pretrain-trinity-b1-s8192.json")
    cfg, mix = lowered.cfg, lowered.mix
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.sliding_window, cfg.n_dense_layers, cfg.d_ff,
            cfg.n_experts, cfg.held, cfg.experts_per_token, cfg.d_expert,
            cfg.d_shared, cfg.routed_scale, cfg.vocab_size) == (
        5, 3072, 48, 8, 128, 4096, 1, 12288, 256, (0, 8), 4, 3072, 3072,
        2.448, 25024)
    assert (mix["global_batch"], mix["seq"], mix["ring_batches"]) == (
        1, 8192, 8)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered text, compiled text, XLA's memory analysis) of that step."""
    compiled = cell.lowered.compile()
    return cell.lowered.as_text(), compiled.as_text(), \
        compiled.memory_analysis()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs")


def test_lowered_step_hands_the_kernels_six_copies_of_eight_heads(cell):
    """Before XLA: the step's Mosaic kernels are the family's five; every
    attention call takes q, k and v as [48, 8192, 128] bfloat16 (k and v
    copied across their groups of six) and lse as a lane row."""
    from chipbench import harness

    lowered = cell.lowered.as_text()
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    calls = mosaic_call_types(lowered,
                              ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"))
    assert {name for name, _ in calls} == {"_fwd_kernel", "_dq_kernel",
                                           "_dkv_kernel"}
    for name, types in calls:
        assert types.count("<48x8192x128xbf16>") >= 4, (name, types)
        assert "<48x1x8192xf32>" in types, (name, types)
        assert "8192x128xf32" not in types, (name, types)


def test_the_plan_keeps_out_and_lse_and_hands_out_what_is_left(cell):
    """`remat_plan` as the step was traced with a chip's 15.75 GiB. State
    12.86 GB, three quarters of the chip; the base set 0.83 GB: a block
    keeps its input, the kernels' output and lse and the router's scores,
    and none of q, k, v (with them: 2.34 GB, 0.6 more than the chip has);
    the reserve 1.30 GB, the dense block's named values (its [8192, 24576]
    gate | up among them) or the loss; what is left, 0.82 GB, goes to q
    and k at kv-head width in all five blocks and the routing's choices in
    the four expert layers, then to layer 0's three projections."""
    plan = cell.plan
    assert plan.state_bytes == 12_857_647_112
    assert plan.base_bytes == 830_474_240
    assert plan.reserve_bytes == 1_296_040_480
    assert plan.capacity == int(HBM_BYTES)
    names = {name for layer in plan.extras for name in layer}
    assert names <= {"attention_q_proj", "attention_kv_proj",
                     "attention_gate_proj", "attention_k_heads",
                     "flash_attention_q", "mlp_gate_up", "moe_choice",
                     "moe_shared_up"}
    assert plan.extras == (
        ("attention_gate_proj", "attention_k_heads", "attention_kv_proj",
         "attention_q_proj", "flash_attention_q"),
        *(("attention_k_heads", "flash_attention_q", "moe_choice"),) * 4)
    assert plan.kept_extra_bytes == 823_656_576
    assert plan.state_bytes + plan.base_bytes + plan.reserve_bytes \
        + plan.kept_extra_bytes <= HBM_BYTES - 2 ** 30


def test_step_calls_the_kernels_once_a_layer_under_the_scopes(step):
    """Each of the five attention blocks calls its three kernels once
    though remat is on and the block keeps no q, k or v (out and lse are
    kept: the forward kernel does not run again); the four windowed layers'
    calls carry `_window`, the full layer's the names every cell has."""
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled, _ = step
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in (*SCOPES, *family.WINDOW_KERNEL_ROWS):
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    for scope in ("attention_gate", "moe_route"):
        assert scope in profiling.DEVICE_SCOPES
    assert "/windowed_attention_mixer/attention_gate/" in compiled
    assert "/attention_mixer/attention_gate/" in compiled
    assert "/channel_mixer/moe_route/" in compiled
    calls = profiling.kernel_calls(compiled)
    assert {k: v for k, v in calls.items() if "flash" in k} == {
        "flash_attention_fwd_window": 4, "flash_attention_dq_window": 4,
        "flash_attention_dkv_window": 4, "flash_attention_fwd": 1,
        "flash_attention_dq": 1, "flash_attention_dkv": 1}
    assert calls["grouped_matmul_dlhs"] == calls["grouped_matmul_drhs"] == 8


def test_step_fits_a_chip_by_xlas_own_total(step, cell, record_property):
    mem = step[2]
    nbytes = total(mem)
    record_property("trinity_b1_s8192_bytes", nbytes)
    print(f"trinity-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    plan = cell.plan
    # XLA's own total stays a GiB under the chip's 15.75 GiB, and under
    # what the plan reckoned: state, the base set, the reserve, and what
    # is kept beside.
    assert nbytes <= HBM_BYTES - 2 ** 30
    assert nbytes <= plan.state_bytes + plan.base_bytes \
        + plan.reserve_bytes + plan.kept_extra_bytes
    # 15,171,019,776 (my compile, PR 65); 16.54 GB with the projections
    # of every layer taken before q (models/decoder.py _GATED_GQA_FITS)
    assert nbytes <= 15_250_000_000
