"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `glm47flash-train-1chip` cell as the cell runs it —
GLM-4.7-Flash at its published widths (d 2048, latent attention of 20 heads
of 192 + 64 | 256 behind latents of 768 and 512, a dense SwiGLU of 10240, a
64-wide router over 16 held SwiGLU experts of 1536 beside a shared one, V
38,720 untied), layers 0-4 and the multi-token-prediction module behind
them (one more expert-layer block, a second loss through the one head), B=1
x S=16384, remat on, AdamW at the family's rate — compiles for one chip,
calls exactly the attention and grouped-matmul kernels under the program's
scopes, six attention forwards and none twice though remat is on, at q, k
and v all 256 wide on the grid `attention_plan` gives, never holds a
[20, 16384, 16384] map, carries the module's scopes and both losses', and
fits the chip by XLA's memory analysis with `remat_plan`'s reserve counting
the module's block and the second loss (PERF.md section 4 has the figures).
The XLA compile is 60 s of one worker (42 Mosaic kernels), under half of
Xing4.0's, and stays in tier-1.
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import glm4_moe_lite as family
from compile_v5e import (HBM_BYTES, lowered_cell_step,  # noqa: F401
                         assert_flash_rows_are_lane_rows, mosaic_grids,
                         topo, total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, family, "configs/glm-4.7-flash.json",
        "traffic/pretrain-glm47flash-b1-s16384.json")
    cfg, mix = lowered.cfg, lowered.mix
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_predict_layers,
            cfg.d_model, cfg.n_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.d_ff, cfg.n_experts, cfg.held,
            cfg.experts_per_token, cfg.d_expert, cfg.d_shared,
            cfg.vocab_size, cfg.mtp_loss_weight) == (
        5, 1, 1, 2048, 20, 192, 64, 256, 768, 512, 10240, 64, (0, 16), 4,
        1536, 1536, 38720, 0.3)
    assert (mix["global_batch"], mix["seq"]) == (1, 16384)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered text, compiled text, XLA's memory analysis) of that step."""
    compiled = cell.lowered.compile()
    return cell.lowered.as_text(), compiled.as_text(), \
        compiled.memory_analysis()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs")


def test_lowered_step_calls_the_five_kernels_at_256_and_256_with_no_map(cell):
    """Before XLA: the step's Mosaic kernels are the family's five, every
    flash forward call is handed q, k and v [20, 16384, 256], and no value
    is a [20, 16384, 16384] map."""
    from chipbench import harness

    lowered = cell.lowered.as_text()
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    calls = [line for line in lowered.splitlines()
             if "@tpu_custom_call" in line
             and 'kernel_name = "_fwd_kernel"' in line]
    assert calls        # (one function a call site's shapes: few lines)
    for line in calls:
        types = line[line.rindex("} : ("):]
        assert len(re.findall(r"<(?:1x)?20x16384x256xbf16>", types)) >= 4
        assert "16384x192x" not in types
    assert "20x16384x16384" not in lowered


def test_the_three_kernels_run_the_grid_the_plan_says(cell):
    """`attention_plan(16384, 256, v_dim=256)`: forward and dQ hold 1,024
    queries against K and V in FOUR grid blocks of 4,096, 16 x 4 programs a
    head, dK/dV 1,024 keys against queries in FOUR of 4,096 too (eight of
    2,048 until PR 58, beside lse and delta blocks of [2048, 128]; they are
    lane rows [1, 4096] now), and the lowered step's Mosaic calls carry
    those grids and blocks."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_plan

    plan = attention_plan(16384, 256, True, jnp.bfloat16, None, 256)
    for kernel in (plan.fwd, plan.dq):
        assert (kernel.block, kernel.swept, kernel.tiles) == (1024, 4096, 184)
    assert (plan.dkv.block, plan.dkv.swept) == (1024, 4096)
    grids = mosaic_grids(cell.lowered.as_text(),
                          ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"))
    own, swept, row = (1, 1024, 256), (1, 4096, 256), (1, 1, 1024)
    assert grids["_fwd_kernel"] == {((20, 16, 4), (own, swept, swept, own,
                                                   row))}
    (grid, blocks), = grids["_dq_kernel"]
    assert grid == (20, 16, 4)
    assert blocks[:6] == (own, swept, swept, own, row, row)
    (grid, blocks), = grids["_dkv_kernel"]
    assert grid == (20, 16, 4)
    assert blocks[:6] == (swept, own, own, swept, (1, 1, 4096), (1, 1, 4096))


def test_the_kernels_rows_are_four_bytes_a_position(cell):
    """lse and delta, the flash kernels' two per-row float32 residuals, are
    lane rows [20, 1, 16384] in the lowered step: the forward's second
    result, the last two operands of dQ and of dK/dV, and no operand or
    result of the three is [.., 16384, 128] float32 (512 bytes a row, 168 MB
    a block each, until PR 58). By the account a block keeps 1.3 MB of lse:
    `test_the_plan_counts_the_module_and_the_second_loss` holds the base
    set."""
    assert_flash_rows_are_lane_rows(cell.lowered.as_text(), 20)


def test_the_plan_counts_the_module_and_the_second_loss(cell):
    """`remat_plan` as the step was traced with a chip's 15.75 GiB: six
    blocks (the module's last) and two losses. State 9.31 GB (weights, two
    moments, gradients), the base set 1.57 (a block's input 0.07, the
    kernel's output 0.17, lse 1.3 MB, the latent and shared key 0.02, the
    router's scores: q, 0.17 a layer, is a candidate and not of the base
    set; 2.57 while lse was padded to 128 lanes, 0.17 GB a block), the
    reserve 4.24: the largest block's backward with the 1.01 GB of keys,
    values and cotangents no name shows (`_latent_holds`) and a second
    loss's working set, 1.27 (4.41 with that block's padded lse). Until
    PR 58 that left nothing under the plan's margin and q was made again in
    all six blocks; now 0.71 GB are left, which hold q in the first four
    (168 MB each) and the five expert blocks' routing choices, and 36 MB
    stay: XLA's own total is 15.83 GB where it was 15.97."""
    plan = cell.plan
    q, choice = ("flash_attention_q",), ("moe_choice",)
    assert plan.extras == (q,) + (q + choice,) * 3 + (choice,) * 2
    assert plan.layers_extended == 6
    assert plan.kept_extra_bytes == 4 * 20 * 16384 * 256 * 2 + 5 * (
        3 * 16384 * 4 * 4 + 16 * 4) == 675_021_120
    assert 9.30e9 < plan.state_bytes < 9.32e9
    assert plan.base_bytes == 1_572_341_248
    assert plan.reserve_bytes == 4_242_538_816
    from ray_tpu.ops.loss import working_set_bytes
    loss = working_set_bytes(16384, 2048, 38720)
    assert 1.26e9 < loss < 1.28e9
    assert plan.reserve_bytes - loss > loss        # a block's, not the loss's
    assert 0 < plan.bytes_left == 35_787_384 < 20 * 16384 * 256 * 2


def test_step_calls_exactly_the_five_kernels_under_the_programs_scopes(step):
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
    # the module's scopes reach the compiled step's instructions, its
    # block's own inside `mtp` as any layer's inside `layers`, its loss
    # beside the stack's
    for scope in ("mtp", "mtp_embed", "mtp_project", "mtp_norm", "mtp_loss",
                  "mla_project", "mla_expand", "moe_shared"):
        assert scope in profiling.DEVICE_SCOPES
    for path in ("mtp)/mtp_embed/", "mtp)/mtp_project/", "mtp)/mtp_norm/",
                 "/latent_attention_mixer/mla_project/",
                 "/channel_mixer/moe_shared/", "mtp_loss)/loss/"):
        assert path in compiled, path
    assert re.search(r"mtp\)*/[^\"]*latent_attention_mixer/mla_expand",
                     compiled)
    assert re.search(r"mtp\)*/[^\"]*channel_mixer/moe_route", compiled)


def test_no_attention_forward_runs_twice_and_q_is_made_again(step, cell):
    """Remat is on, and a latent layer's block keeps the kernel's output
    and lse (models/decoder.py KEPT_BY_KIND): each of the six blocks calls
    its forward kernel once, with keys and values made again, and q in the
    last two blocks, which the plan leaves no room to keep it in. The five
    expert layers (the module's among them) call their two forward grouped
    matmuls once and make the first again in the backward rule: 15 calls
    beside 10 gradients by the rows and 10 by the weights (a block joined
    by the add: the rule's residuals are its inputs and the block's second
    forward is dead, as in lfm2moe-train-1chip)."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1]) == {
        "flash_attention_fwd": 6, "flash_attention_dq": 6,
        "flash_attention_dkv": 6, "grouped_matmul_fwd": 15,
        "grouped_matmul_dlhs": 10, "grouped_matmul_drhs": 10}
    assert not re.search(r"\[(1,)?20,16384,16384\]", step[1])
    assert ["flash_attention_q" in names for names in cell.plan.extras] \
        == [True] * 4 + [False] * 2


def test_step_fits_a_chip_by_xlas_own_total(step, cell, record_property):
    mem = step[2]
    nbytes = total(mem)
    record_property("glm47flash_b1_s16384_bytes", nbytes)
    print(f"glm47flash-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    plan = cell.plan
    # XLA's own total: 15.83 GB (15,826,912,768), a GiB under the chip's
    # 15.75 GiB by 11 MB, with q kept in four blocks (15.97 with q made
    # again in all six while lse and delta were padded to 128 lanes; with q
    # kept in every block then, as before PR 55: 17.10 GB, over the chip's).
    # What the plan reckoned is 15.80: from above while nothing was kept
    # (16.29 against 15.97), 25 MB under now, because XLA's total is not
    # additive in the q kept: under forced plans it reads 15.08 GB with q
    # in no block, 15.94 in the first three, 15.83 in four, 15.86 in all
    # six (PERF.md section 7, PR 58); the plan's GiB of margin is for that.
    assert nbytes <= HBM_BYTES - 2 ** 30
    assert nbytes <= plan.state_bytes + plan.base_bytes \
        + plan.reserve_bytes + plan.kept_extra_bytes + 2 ** 25
