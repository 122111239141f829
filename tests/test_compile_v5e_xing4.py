"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `xing4-train-1chip` cell as the cell runs it —
Xing4.0-29B-A4B at its published widths (d 3584, four residual streams
joined by Sinkhorn-normalised hyper-connections, latent attention of 32
heads of 128 + 64 | 128 behind latents of 768 and 512 at YaRN's
frequencies, a dense SwiGLU of 9216, a 64-wide router over 8 held SwiGLU
experts of 1024 beside a gated shared one, V 16,384 untied), layer 0 and
layers 2-5, B=1 x S=16384, remat on, AdamW at the family's rate — compiles
for one chip, calls exactly the attention and grouped-matmul kernels under
the program's scopes, no forward kernel twice though remat is on, keeps
the latent and no per-head key or value, never holds a [32, 16384, 16384]
map, a float32 copy of the streams or a row residual padded to 128 lanes,
and fits the chip by XLA's memory
analysis (PERF.md section 4 has the figure). What the LOWERED step shows
(the five kernels, their operands' widths, no attention map, what the plan
kept) is tier-1's; what only XLA's compile shows (the scopes on the
compiled instructions, how often each kernel runs, no float32 copy of the
streams, XLA's total) is marked `slow`: that compile is 140 s of one
worker alone and 560 CPU-seconds (51 Mosaic kernels, 90k instructions),
and tier-1 stood at 1,418 s of its 1,470 with it (CHANGES.md, PR 53).
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import xing4
from compile_v5e import (HBM_BYTES, lowered_cell_step,  # noqa: F401
                         assert_flash_rows_are_lane_rows, mosaic_grids,
                         topo, total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, xing4, "configs/xing4.0-29b-a4b.json",
        "traffic/pretrain-xing4-b1-s16384.json")
    cfg, mix = lowered.cfg, lowered.mix
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_model, cfg.n_heads,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.q_lora_rank, cfg.kv_lora_rank, cfg.d_ff, cfg.n_experts,
            cfg.held, cfg.experts_per_token, cfg.d_expert, cfg.d_shared,
            cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.vocab_size) == (
        5, 1, 3584, 32, 128, 64, 128, 768, 512, 9216, 64, (0, 8), 4, 1024,
        1024, 4, 20, 16384)
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


def test_lowered_step_calls_the_five_kernels_at_192_and_128_with_no_map(cell):
    """Before XLA: the step's Mosaic kernels are the family's five, every
    flash forward call is handed q and k 192 wide and v 128 wide (no head
    padded to 256), and no value is a [32, 16384, 16384] map."""
    from chipbench import harness

    lowered = cell.lowered.as_text()
    assert harness.mosaic_kernel_names(lowered) == set(xing4.MOSAIC_KERNELS)
    calls = [line for line in lowered.splitlines()
             if "@tpu_custom_call" in line
             and 'kernel_name = "_fwd_kernel"' in line]
    assert calls        # (one function a call site's shapes: few lines)
    for line in calls:
        types = line[line.rindex("} : ("):]
        assert re.search(r"<(1x)?32x16384x192xbf16>", types), types
        assert re.search(r"<(1x)?32x16384x128xbf16>", types), types
        assert "16384x256x" not in types
    assert "32x16384x16384" not in lowered


def test_forward_and_dq_run_the_grid_the_plan_says(cell):
    """`attention_plan(16384, 192, v_dim=128)` sizes a kernel's own block
    first: forward and dQ hold 1,024 queries against K and V in two grid
    blocks of 8,192, 16 x 2 programs a head, and the lowered step's Mosaic
    calls carry that grid and those blocks (a score tile 1,024 wide, as
    every other 16k shape has). dK/dV holds 1,024 keys against the queries
    in two grid blocks of 8,192 as well (four of 4,096 until PR 58: the
    queries' lse and delta are a lane row each, [1, 8192], where they were
    [4096, 128]), and a kernel's own rows' lse and delta are [1, 1024]."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_plan

    plan = attention_plan(16384, 192, True, jnp.bfloat16, None, 128)
    for kernel in (plan.fwd, plan.dq):
        assert (kernel.block, kernel.swept, kernel.tiles) == (1024, 8192, 184)
    grids = mosaic_grids(cell.lowered.as_text(),
                          ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"))
    q, k, v = (1, 1024, 192), (1, 8192, 192), (1, 8192, 128)
    o, row = (1, 1024, 128), (1, 1, 1024)
    assert grids["_fwd_kernel"] == {((32, 16, 2), (q, k, v, o, row))}
    assert grids["_dq_kernel"] == {((32, 16, 2), (q, k, v, o, row, row, q))}
    (grid, blocks), = grids["_dkv_kernel"]
    assert (plan.dkv.block, plan.dkv.swept) == (1024, 8192)
    assert grid == (32, 16, 2)
    assert blocks[:6] == ((1, 8192, 192), (1, 1024, 192), (1, 1024, 128),
                          (1, 8192, 128), (1, 1, 8192), (1, 1, 8192))


def test_the_kernels_rows_are_four_bytes_a_position(cell):
    """lse and delta, the flash kernels' two per-row float32 residuals, are
    lane rows [32, 1, 16384] in the lowered step: the forward's second
    result, the last two operands of dQ and of dK/dV, and no operand or
    result of the three is [.., 16384, 128] float32 (512 bytes a row, 268 MB
    a layer each, until PR 58). By the account a layer keeps 2 MB of lse:
    `test_the_plan_says_what_the_blocks_keep` holds the base set."""
    assert_flash_rows_are_lane_rows(cell.lowered.as_text(), 32)


def test_the_plan_says_what_the_blocks_keep(cell):
    """`remat_plan` as the step was traced with a chip's 15.75 GiB: state
    6.08 GB (weights, two moments, gradients), the base set 3.16 (a layer's
    four streams 0.47, the output 0.13, lse 2 MB, the latent and shared key
    0.02, the router's scores; 4.49 while lse was padded to 128 lanes, 0.27
    GB a layer), the reserve 2.99 of which the streams a hyper-connected
    block's backward holds are 1.17 (`_streams_hold`: two and a half values
    of their size since PR 56, four while the channel branch was made
    again; 3.26 with the largest block's padded lse), 3.60 GB of room.
    First what stands first: every latent block's q (0.20 GB a layer) and
    the four expert layers' routing choices (6 MB); then every layer's
    channel branch's output (`hc_channel_out`, 0.12 GB a layer, four
    matmuls an element: the expert layers' forward no longer runs twice);
    then at a matmul an element the four shared experts' up projections
    (0.07 a layer) and, since PR 58, the dense layer's gate and up (0.60):
    every candidate the table has, 1.13 left."""
    plan = cell.plan
    assert plan.extras == (
        ("flash_attention_q", "hc_channel_out", "mlp_gate_up"),) + (
        ("flash_attention_q", "hc_channel_out", "moe_choice",
         "moe_shared_up"),) * 4
    assert plan.layers_extended == 5
    q = 32 * 16384 * 192 * 2
    branch = 16384 * 3584 * 2           # a branch's output, bfloat16
    choices = 3 * 16384 * 4 * 4 + 8 * 4     # k = 4, 8 held experts
    shared_up = 16384 * 2048 * 2
    gate_up = 2 * 16384 * 9216 * 2
    assert plan.kept_extra_bytes == 5 * (q + branch) \
        + 4 * (choices + shared_up) + gate_up == 2_469_396_608
    # five layers' lse at 4 bytes a row where it was 512
    assert plan.base_bytes == 4_490_003_712 - 5 * 32 * 16384 * (512 - 4) \
        == 3_158_312_192
    assert plan.reserve_bytes == 3_259_760_928 - 32 * 16384 * (512 - 4) \
        == 2_993_422_624
    assert plan.bytes_left == 1_134_081_144 == (
        int(HBM_BYTES) - 2 ** 30 - plan.state_bytes - plan.base_bytes
        - plan.reserve_bytes - plan.kept_extra_bytes)


@pytest.mark.slow
def test_step_calls_exactly_the_five_kernels_under_the_programs_scopes(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled, _ = step
    assert harness.mosaic_kernel_names(lowered) == set(xing4.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in SCOPES:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    # the XLA scopes this family brought reach the compiled step's
    # instructions, each branch's three inside the branch's own
    for scope in ("hc_coefficients", "hc_read", "hc_write", "mla_project",
                  "mla_expand", "moe_shared"):
        assert scope in profiling.DEVICE_SCOPES
        assert f"/{scope}/" in compiled, scope
    for branch in ("latent_attention_mixer", "channel_mixer"):
        assert f"/{branch}/hc_write/" in compiled


@pytest.mark.slow
def test_no_forward_kernel_runs_twice_the_experts_neither(step):
    """Remat is on, and a latent layer's block keeps q, the kernel's output
    and lse (models/decoder.py KEPT_BY_KIND and the plan): each of the five
    layers calls its forward kernel once, with keys and values made again
    from the kept latent. The four expert layers call their two forward grouped matmuls
    (gate | up as one, down) ONCE, and make the first again in the backward
    rule: 12 calls beside 8 gradients by the rows and 8 by the weights, as
    a held-expert block joined by the add has (lfm2moe). Until PR 56 there
    were 20: H_post's gradient reads the branch's output, which no kept
    name held, so the block's second forward of the experts lived; every
    layer keeps `hc_channel_out` now and it is dead code (the rule's
    residuals are its inputs)."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1]) == {
        "flash_attention_fwd": 5, "flash_attention_dq": 5,
        "flash_attention_dkv": 5, "grouped_matmul_fwd": 12,
        "grouped_matmul_dlhs": 8, "grouped_matmul_drhs": 8}


@pytest.mark.slow
def test_the_kernels_run_at_192_and_128_and_no_map_or_wide_copy_exists(step):
    """The flash kernels are handed q and k [32, 16384, 192] and v
    [32, 16384, 128]: no head is padded to 256. Nothing of the step is a
    [32, 16384, 16384] map, and no value is the four streams in float32
    (0.94 GB each: the mixes accumulate in float32 inside their fusions)."""
    compiled = step[1]
    calls = [line for line in compiled.splitlines()
             if "tpu_custom_call" in line and "flash_attention_fwd" in line
             and " = " in line]
    assert len(calls) == 5
    for line in calls:
        assert "bf16[32,16384,192]" in line and "bf16[32,16384,128]" in line
        assert "16384,256]" not in line
    assert not re.search(r"\[(1,)?32,16384,16384\]", compiled)
    assert not re.search(r"f32\[(1,)?16384,(4,3584|14336)\]", compiled)
    # the streams are four bf16 [16384, 3584] values
    assert "bf16[1,16384,3584]" in compiled or "bf16[16384,3584]" in compiled


@pytest.mark.slow
def test_step_fits_a_chip_by_xlas_own_total(step, record_property):
    mem = step[2]
    nbytes = total(mem)
    record_property("xing4_b1_s16384_bytes", nbytes)
    print(f"xing4-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # XLA's own total: 14.95 GB (14,951,981,568), 1.83 GiB under the chip's
    # 15.75 GiB; the plan's own sum is 14.70. Until PR 58, with lse and
    # delta padded to 128 lanes and without the dense layer's gate and up:
    # 15.57 (the base set alone 13.80; PR 55's plan 15.46).
    assert nbytes < 15.0e9
    assert nbytes <= HBM_BYTES - 2 ** 30
    # The five layers' code is emitted once and called (`_SHARED_CODE`,
    # models/_training.py): 118,878,720 bytes of it. Left to the compiler's
    # own rule this step, with 1.8 GiB to spare, reads 576,258,048, its
    # executable 647 MB serialized where the parent's was 189, and the
    # cell's programs no longer fit its machines' compile cache.
    assert mem.generated_code_size_in_bytes < 150e6
