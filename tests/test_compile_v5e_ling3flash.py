"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `ling3flash-train-1chip` cell as the cell runs it —
Ling-3.0-flash at its published widths (d 2560; five KDA layers of 32 heads
of 128 | 128 with a decay a key channel and one latent-attention layer of 32
heads of 192 | 128 over a 512-wide latent, both under a head-wise gate; a
512-wide sigmoid router in 8 groups over 16 held SwiGLU experts of 768 and
a shared one; V 19,648 untied), one period of six layers, B=1 x S=16384,
remat on, AdamW at the family's rate — compiles for one chip, calls exactly
the attention, KDA and grouped-matmul kernels under the program's scopes,
each rule kernel once a layer though remat is on, and fits the chip by
XLA's memory analysis with `remat_plan`'s reserve counting the float32
log-decay's values (PERF.md section 4 has the figures). The XLA compile is
under a minute of one worker: ONE compile, which every case of the
compiled step reads. tests/compile_v5e.py has the described topology and
the lowering."""

import re

import pytest

from chipbench.families import bailing_hybrid as family
from compile_v5e import (HBM_BYTES, lowered_cell_step,  # noqa: F401
                         assert_flash_rows_are_lane_rows, mosaic_call_types,
                         mosaic_grids, topo, total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, family, "configs/ling-3.0-flash.json",
        "traffic/pretrain-ling3flash-b1-s16384.json")
    cfg, mix = lowered.cfg, lowered.mix
    assert (cfg.n_layers, cfg.layer_group_size, cfg.d_model, cfg.n_heads,
            cfg.kda_head_dim, cfg.conv_taps, cfg.kda_lower_bound,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.n_dense_layers, cfg.d_ff, cfg.n_experts,
            cfg.held, cfg.experts_per_token, cfg.n_group, cfg.topk_group,
            cfg.d_expert, cfg.routed_scale, cfg.vocab_size) == (
        6, 6, 2560, 32, 128, 4, -5.0, 128, 64, 128, 512, 1, 6144, 512,
        (0, 16), 8, 8, 4, 768, 2.5, 19648)
    assert (mix["global_batch"], mix["seq"], mix["ring_batches"]) == (
        1, 16384, 8)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered text, compiled text, XLA's memory analysis) of that step."""
    compiled = cell.lowered.compile()
    return cell.lowered.as_text(), compiled.as_text(), \
        compiled.memory_analysis()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "kda_fwd", "kda_bwd", "grouped_matmul_fwd", "grouped_matmul_dlhs",
          "grouped_matmul_drhs")


def test_lowered_step_hands_the_rule_a_decay_a_channel(cell):
    """Before XLA: the step's Mosaic kernels are the family's seven; the
    KDA forward kernel takes q, k, v [1, 16384, 4096] bfloat16, the log-decay
    g [1, 16384, 4096] float32 (gated_delta's is a [.., 30]) and beta by
    head group, and leaves o, the state entering each of 256 chunks in the
    model's dtype and T - I; the backward kernel reads those and writes a
    float32 gradient a channel; the grid is (batch, 4 groups of 8 heads, 256
    chunks)."""
    from chipbench import harness

    lowered = cell.lowered.as_text()
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    (_, fwd), = set(mosaic_call_types(lowered, ("_kda_fwd_kernel",)))
    (_, bwd), = set(mosaic_call_types(lowered, ("_kda_bwd_kernel",)))
    for types in (fwd, bwd):
        assert "<1x16384x4096xf32>" in types
        assert "<1x4x16384x8xf32>" in types                 # beta, 8 a group
        assert "<1x256x32x128x128xbf16>" in types           # chunk states
        assert "<1x256x64x2048xbf16>" in types              # T - I
        assert len(re.findall(r"<1x16384x4096xbf16>", types)) >= 4
    assert bwd.count("<1x16384x4096xf32>") == 2             # g in, dg out
    grids = mosaic_grids(lowered, ("_kda_fwd_kernel", "_kda_bwd_kernel"))
    for kernel in ("_kda_fwd_kernel", "_kda_bwd_kernel"):
        (grid, blocks), = grids[kernel]
        assert grid == (1, 4, 256)
        assert blocks[:3] == ((1, 64, 1024),) * 3
    # the latent layer's kernels at xing4-train-1chip's shape
    for name, types in mosaic_call_types(
            lowered, ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")):
        assert "<32x16384x192xbf16>" in types, name
        assert "<32x16384x128xbf16>" in types, name
    assert_flash_rows_are_lane_rows(lowered, 32)


def test_the_plan_counts_the_log_decay_and_the_chunk_states(cell):
    """`remat_plan` as the step was traced with a chip's 15.75 GiB. State
    7.66 GB; the base set 3.34: a KDA block keeps its input, the rule's
    output, the chunk states and T - I (0.55 GB with its router's scores);
    the reserve 3.91: the largest block's named values and eight float32
    [16384, 4096] values under no name (`_kda_holds`); what is left holds
    layer 0's q | k | v projection, every expert layer's choices and shared
    up projection and the latent layer's q, 0.86 GB. No layer keeps its
    float32 log-decay."""
    plan = cell.plan
    assert plan.state_bytes == 7_662_180_872
    assert plan.base_bytes == 3_342_860_544
    assert plan.reserve_bytes == 3_906_469_952 > 8 * 16384 * 4096 * 4
    assert plan.extras == (
        ("kda_in",), *(("moe_choice", "moe_shared_up"),) * 4,
        ("flash_attention_q", "moe_choice", "moe_shared_up"))
    assert plan.kept_extra_bytes == 863_502_656
    assert plan.layers_extended == 6


def test_step_calls_the_seven_kernels_once_a_layer_under_the_scopes(step):
    """Each of the five KDA blocks calls the rule's forward kernel once and
    its backward kernel once though remat is on (the block keeps o, the
    states and T - I); the latent block its three; the five expert layers
    their two forward grouped matmuls once and make the first again in the
    backward rule (glm47flash-train-1chip's counts a layer)."""
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled, _ = step
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in SCOPES:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    assert set(family.KDA_KERNEL_ROWS) <= set(SCOPES)
    assert not any("gated_delta" in r or "flash_attention" in r
                   for r in rows if "kda" in r)
    for scope in ("ssm_conv", "kda_qk_norm", "kda_gate", "kda_gate_norm"):
        assert scope in profiling.DEVICE_SCOPES
        assert f"/kda_mixer/{scope}/" in compiled, scope
    assert "/latent_attention_mixer/mla_gate/" in compiled
    assert "/channel_mixer/moe_route/" in compiled
    # the float32 log-decay keeps the projections' own layout from the gate
    # to the kernels and back: as a [16384, 32, 128] residual it was another
    # tiling and twenty copies a step ([2048, 8, 32, 128] as XLA cut it)
    assert "f32[2048,8,32,128]" not in compiled
    # and its running sums are the kernels' own: XLA sums no float32
    # [16384, 4096] value along its rows round them, either way
    summed = [line for line in compiled.splitlines()
              if ("/kda_fwd" in line or "/kda_bwd" in line)
              and ("reduce-window" in line or "cumsum" in line)
              and re.search(r"f32\[(1,)?(16384|256,64),4096\]", line)]
    assert not summed, summed[:2]
    assert profiling.kernel_calls(compiled) == {
        "flash_attention_fwd": 1, "flash_attention_dq": 1,
        "flash_attention_dkv": 1, "kda_fwd": 5, "kda_bwd": 5,
        "grouped_matmul_fwd": 15, "grouped_matmul_dlhs": 10,
        "grouped_matmul_drhs": 10}


def test_step_fits_a_chip_by_xlas_own_total(step, cell, record_property):
    mem = step[2]
    nbytes = total(mem)
    record_property("ling3flash_b1_s16384_bytes", nbytes)
    print(f"ling3flash-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    plan = cell.plan
    # XLA's own total stays a GiB under the chip's 15.75 GiB, and under
    # what the plan reckoned: state, the base set, the reserve with its
    # eight float32 values, and what is kept beside.
    assert nbytes <= HBM_BYTES - 2 ** 30
    assert nbytes <= plan.state_bytes + plan.base_bytes \
        + plan.reserve_bytes + plan.kept_extra_bytes
    # 12,761,842,176 since PR 64, the running sums of g made inside the
    # kernels: 15,571,643,904 on PR 63's tree, whose two window sums a
    # layer XLA padded and held beside their float32 operands
    # (17,313,021,952 with the chunk states float32 and no such reserve:
    # over the chip)
    assert nbytes <= 12_830_000_000
