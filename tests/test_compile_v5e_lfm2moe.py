"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `lfm2moe-train-1chip` cell as the cell runs it —
LFM2-8B-A1B at its published widths (d 2048, gated short convolutions of
three taps, attention 32 query heads over 8 kv heads x 64 with a norm a
head and rotary at 1e6, a dense SwiGLU of 7168, a 32-wide router over 16
held SwiGLU experts of 1792, V 32,768 tied), layer 0 and layers 2-5, B=4 x
S=8192, remat on, the default optimizer — compiles for one chip, calls
exactly the attention, grouped-matmul and short-convolution kernels under
the program's scopes, no attention forward twice though remat is on, the
expert layers' buffers at the held rows and none at T x k, and fits the
chip by XLA's memory analysis (PERF.md section 4 has the figure).
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import lfm2_moe
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, lfm2_moe, "configs/lfm2-8b-a1b.json",
        "traffic/pretrain-lfm2moe-s8192.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.layer_types, cfg.n_dense_layers, cfg.d_model,
            cfg.conv_taps, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.n_experts, cfg.held, cfg.experts_per_token,
            cfg.d_expert, cfg.topk_weight_eps, cfg.vocab_size) == (
        5, ("conv", "full_attention", "conv", "conv", "conv"), 1, 2048, 3,
        32, 8, 64, 7168, 32, (0, 16), 4, 1792, 1e-6, 32768)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs",
          "short_conv_fwd", "short_conv_bwd")


def test_step_calls_exactly_the_three_families_of_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        lfm2_moe.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in SCOPES:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    # the XLA scope round the convolution mixer's two projections reaches
    # the compiled step's instructions
    assert "short_conv_proj" in profiling.DEVICE_SCOPES
    assert "/short_conv_proj/" in compiled.as_text()


def test_which_forward_kernels_run_twice_a_step(step):
    """Remat is on, and a block keeps what its kernels made
    (models/decoder.py KEPT_UNDER_REMAT): the one attention layer calls its
    forward kernel once. A convolution layer keeps nothing (its kernels'
    residuals are their inputs), so its block's second forward runs the
    forward kernel again: 8 calls for 4 backward. The four expert layers
    call their two forward grouped matmuls (gate | up as one, down) and
    make the first again in the backward rule, 12 calls beside 8 gradients
    by the rows and 8 by the weights: the rule's residuals are its inputs
    (parallel/moe.py `_held_experts_fwd`), so the block's second forward
    has nothing the rule reads and is gone. A pass is a loop's body, which
    is counted once."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1].as_text()) == {
        "flash_attention_fwd": 1, "flash_attention_dq": 1,
        "flash_attention_dkv": 1, "short_conv_fwd": 8, "short_conv_bwd": 4,
        "grouped_matmul_fwd": 12, "grouped_matmul_dlhs": 8,
        "grouped_matmul_drhs": 8}


def test_the_convolution_kernels_read_the_projection_in_place(step):
    """The forward kernel is handed the [4, 8192, 6144] projection itself
    (its B, C and x thirds and their halos are blocks of that one array)
    and writes [4, 8192, 2048]; the backward writes the projection's
    gradient whole, a third a grid step, and the taps' as eight float32
    partial rows a tap."""
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    forward = [line for line in entry.splitlines()
               if "tpu_custom_call" in line and "short_conv_fwd" in line]
    backward = [line for line in entry.splitlines()
                if "tpu_custom_call" in line and "short_conv_bwd" in line]
    assert len(forward) == 8 and len(backward) == 4
    for line in forward:
        assert line.split(" = ")[1].startswith("bf16[4,8192,2048]")
        assert "bf16[4,8192,6144]" in line
    for line in backward:
        assert "bf16[4,8192,6144]" in line.split(" = ")[1].split(")")[0]
        assert "f32[3,8,2048]" in line


def test_the_experts_buffers_hold_the_held_rows_and_nothing_is_dropped(step):
    """A pass's rows are [R, d] = [73728, 2048], [R, 2f] = [73728, 3584]
    and [R, f] = [73728, 1792], a balanced share and an eighth in 144 row
    tiles (parallel/moe.py `held_rows_plan`), walked in a loop as often as
    the routing needs: nothing of the step has the T x k = 131,072
    assignments for an axis but the index vectors, and there is no
    capacity and no [T, E, C] dispatch tensor."""
    from ray_tpu.parallel.moe import held_rows_plan
    from ray_tpu.util import profiling

    assert held_rows_plan(32768, 4, 16, 32) == (73728, 65536, 512, True)
    text = step[1].as_text()
    for shape in ("bf16[73728,2048]", "bf16[73728,3584]", "bf16[73728,1792]",
                  "bf16[16,2048,3584]", "bf16[16,1792,2048]"):
        assert shape in text, shape
    # a buffer is what an instruction outside a fusion's body makes (the
    # entry's and the loops' own): inside one, [131072, 32] is the
    # router's counting compared and summed in registers
    bodies = profiling._computations(text)
    fused = {profiling._CALLEE.search(rest).group(1)
             for body in bodies.values() for _, _, op, rest in body
             if op == "fusion"}
    long = {shape for name, body in bodies.items() if name not in fused
            for _, shapes, _, _ in body
            for shape in re.findall(r"\w+\[(?:\d+,)*131072(?:,\d+)*\]",
                                    shapes)}
    assert long and all(re.fullmatch(r"\w+\[(1,)?131072(,1)?\]", shape)
                        for shape in long), long
    assert not re.search(r"\[32768,32,\d+\]", text)       # [T, E, C]
    assert " while(" in text                # the passes are one loop's


def test_a_pass_s_rows_come_back_to_their_tokens_by_gathers(step):
    """T x k = 131,072 rows gathered against 73,728 scattered, 1.78 for
    one: the plan names the gathers (parallel/moe.py `_gathered_back`), so
    the step scatters no row into the tokens' float32 [T, d], forward
    (`moe_combine`) or backward (`moe_dx`), where it held 8 such
    scatter-adds (PERF.md section 6, PR 49). What it still scatters: the
    embedding's gradient into the [V, d] table (V is 32,768 too), each
    layer's k router weights' gradients into its [T * E] scores, and the
    grouped matmuls' tile counts. Each of the eight sums is k = 4 gathers
    of `bf16[32768,2048]` from a pass's `[73728,2048]`, never one value
    with a k axis."""
    from ray_tpu.parallel.moe import held_rows_plan
    from ray_tpu.util import profiling

    assert held_rows_plan(32768, 4, 16, 32).gathered
    text = step[1].as_text()
    calls = profiling.scatter_calls(text)
    assert "f32[32768,2048]" not in calls, calls
    assert {into: n for into, n in calls.items()
            if not into.startswith("s32[")} == {
        "bf16[32768,2048]": 1, "f32[1048576]": 4}, calls
    for scope in ("moe_combine", "moe_dx"):
        gathers = [line for line in text.splitlines()
                   if " gather(" in line and f"/{scope}/" in line]
        assert len(gathers) == 16, (scope, len(gathers))
        assert all(line.split(" = ")[1].startswith("bf16[32768,2048]")
                   for line in gathers)
    assert not re.search(r"\[32768,4,2048\]|\[4,32768,2048\]", text)


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("lfm2moe_b4_s8192_bytes", nbytes)
    print(f"lfm2moe-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # PR 48's figure at B=4 (15.25 GB, the base set's still: 15,247,972,352
    # by PR 51's compile): a tenth of a GB above it. What the held experts'
    # backward holds left room for the four expert layers' routing
    # choices (1.5 MB each) and for no projection: 15,090,703,360 bytes.
    # Since PR 58 the attention layer's lse is 4 bytes a row (0.53 GB less
    # in the base set at B=4) and the dense layer's gate and up fit (0.94
    # GB), not its convolution's input projection beside them (0.40: with
    # both XLA gave 15,900,116,480, over the line, where the account said
    # 71 MB were left; `_reserve` counts the cotangent of the block's
    # output beside the held experts' rule since): 15,497,882,624 bytes.
    plan = cell.plan
    assert plan.extras == (("mlp_gate_up",),) + (("moe_choice",),) * 4
    assert nbytes < 15.55e9
    # from above, since PR 58 by 0.5 MB
    assert nbytes <= plan.state_bytes + plan.base_bytes \
        + plan.reserve_bytes + plan.kept_extra_bytes
    assert nbytes <= HBM_BYTES - 2 ** 30
