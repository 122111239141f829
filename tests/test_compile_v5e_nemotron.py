"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `nemotron3nano-train-1chip` cell as the cell runs it —
NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths (d 2688, Mamba-2 64
heads x 64 in 8 groups with state 128 and chunks of 128, attention 32 query
heads over 2 kv heads x 128, 128-wide router over 16 held relu^2 experts of
1856 and a shared expert of 3712, V 16,384), the first nine layers MEMEM*EME,
B=1 x S=16384, remat on, the default optimizer — compiles for one chip,
calls exactly the attention, scan and grouped-matmul kernels under the
program's scopes, no scan or attention forward twice though remat is on,
the scan's at 8 groups, the gated norm's groups without an axis of their
own, and fits the chip by XLA's memory analysis (PERF.md §4 has the
figure).
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import nemotron_h
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, nemotron_h, "configs/nemotron-3-nano-30b-a3b.json",
        "traffic/pretrain-nemotron3nano-b1-s16384.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.pattern, cfg.d_model, cfg.mamba_n_heads,
            cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups,
            cfg.mamba_chunk_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.n_experts, cfg.held, cfg.experts_per_token, cfg.d_expert,
            cfg.d_shared, cfg.vocab_size) == (
        9, "MEMEM*EME", 2688, 64, 64, 128, 8, 128, 32, 2, 128, 128, (0, 16),
        6, 1856, 3712, 16384)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


def test_step_calls_exactly_the_three_families_of_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        nemotron_h.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    scopes = ("flash_attention_fwd", "flash_attention_dq",
              "flash_attention_dkv", "ssm_scan_fwd", "ssm_scan_bwd",
              "grouped_matmul_fwd", "grouped_matmul_dlhs",
              "grouped_matmul_drhs")
    assert all(s in profiling.DEVICE_SCOPES for s in scopes)
    for scope in scopes:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in scopes) for r in rows), rows


def test_which_forward_kernels_run_twice_a_step(step):
    """Remat is on, and a block keeps what its kernels made
    (models/decoder.py KEPT_UNDER_REMAT): four Mamba-2 layers call the
    scan's forward kernel 4 times a step and the one attention layer its
    forward once. The four expert layers call their two forward grouped
    matmuls and make the first of them again in the backward rule, 12
    calls beside 8 gradients by the rows and 8 by the weights: the rule's
    residuals are its inputs (parallel/moe.py `_held_experts_fwd`), so
    the block's second forward has nothing the rule reads and is gone. A
    pass is a loop's body, which is counted once."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1].as_text()) == {
        "ssm_scan_fwd": 4, "ssm_scan_bwd": 4, "flash_attention_fwd": 1,
        "flash_attention_dq": 1, "flash_attention_dkv": 1,
        "grouped_matmul_fwd": 12, "grouped_matmul_dlhs": 8,
        "grouped_matmul_drhs": 8}


def test_the_scan_kernels_read_eight_groups(step):
    """B and C reach the scan kernels as [1, 16384, 8 x 128], a group's 128
    columns a block, and the states as one a chunk of 128 and pair of
    heads; no buffer of the step has two chunk-long axes (the [Q, Q] tiles
    stay in VMEM)."""
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    scans = [line for line in entry.splitlines()
             if "tpu_custom_call" in line and "ssm_scan_fwd" in line]
    assert len(scans) == 4
    for line in scans:
        assert "bf16[1,16384,1024]" in line and "bf16[1,1024,16384]" in line
        assert "f32[1,128,32,128,128]" in line
    assert not re.search(r"\[(?:\d+,)*128,128,128(?:,\d+)*\]", entry.replace(
        "f32[1,128,32,128,128]", ""))


def test_the_experts_buffers_hold_the_held_rows_and_nothing_is_dropped(step):
    """A pass's rows are [R, d] = [13824, 2688] and [R, f] = [13824, 1856],
    a balanced share and an eighth in 27 row tiles (parallel/moe.py
    `held_rows_plan`), walked in a loop as often as the routing needs:
    nothing of the step has the T x k = 98,304 assignments for an axis but
    the index vectors, and there is no capacity and no [T, E, C] dispatch
    tensor."""
    from ray_tpu.parallel.moe import held_rows_plan

    assert held_rows_plan(16384, 6, 16, 128) == (13824, 12288, 512, False)
    text = step[1].as_text()
    assert "bf16[13824,2688]" in text and "bf16[13824,1856]" in text
    assert "bf16[16,2688,1856]" in text
    # a buffer is what an instruction outside a fusion's body makes (the
    # entry's and the loops' own): inside one, [98304, 128] is the
    # router's counting compared and summed in registers
    from ray_tpu.util import profiling

    bodies = profiling._computations(text)
    fused = {profiling._CALLEE.search(rest).group(1)
             for body in bodies.values() for _, _, op, rest in body
             if op == "fusion"}
    long = {shape for name, body in bodies.items() if name not in fused
            for _, shapes, _, _ in body
            for shape in re.findall(r"\w+\[(?:\d+,)*98304(?:,\d+)*\]", shapes)}
    assert long and all(re.fullmatch(r"\w+\[(1,)?98304(,1)?\]", shape)
                        for shape in long), long
    assert not re.search(r"\[16384,128,\d+\]", text)      # [T, E, C]
    assert " while(" in text                # the passes are one loop's


def test_a_pass_s_rows_come_back_to_their_tokens_by_scatter_adds(step):
    """T x k = 98,304 rows gathered against 13,824 scattered, 7.1 for one:
    the plan keeps the scatter-add here (parallel/moe.py `_gathered_back`
    has both shapes' chip numbers), so each of the four expert layers
    scatters into the tokens' float32 [T, d] twice a step, forward
    (`moe_combine`) and backward (`moe_dx`), as before PR 49, and gathers
    no [16384, 2688] rows under either scope."""
    from ray_tpu.parallel.moe import held_rows_plan
    from ray_tpu.util import profiling

    assert not held_rows_plan(16384, 6, 16, 128).gathered
    text = step[1].as_text()
    assert profiling.scatter_calls(text).get("f32[16384,2688]") == 8
    scatters = [line for line in text.splitlines() if " scatter(" in line
                and " f32[16384,2688]" in line.split(" scatter(")[0]]
    assert sum("/moe_combine/" in line for line in scatters) == 4
    assert sum("/moe_dx/" in line for line in scatters) == 4
    assert not [line for line in text.splitlines() if " gather(" in line
                and ("/moe_combine/" in line or "/moe_dx/" in line)
                and "[16384,2688]" in line.split(" gather(")[0]]
    assert not re.search(r"\[16384,6,2688\]|\[6,16384,2688\]", text)


def test_the_gated_norm_stays_in_the_projections_layout(step):
    """Mamba-2's gated norm over eight groups of 512 channels makes no
    array with a groups axis (ops/layers.py `gated_rms_norm`, `head_sums`):
    the step holds no float32 array of rank 4 or more with 8 and 512 side
    by side, as an instruction's result or inside a fusion. While the norm
    reshaped to [..., 8, 512] the step copied
    `f32[2048,8,8,512]{3,2,1,0:T(8,128)}` twelve times, 268 MB each, 3.22 GB
    a step and 9.86 ms of it on the chip: instructions without an
    `op_name`, so no scope counted them and only the shape finds them. What
    the scope `ssm_gate_norm` itself writes a step, by
    `profiling.scope_writes`: 68 instructions and 5,930,745,856 bytes then,
    29 and 3,764,469,760 now (PERF.md section 6, PR 47); the limit is a
    tenth above that."""
    from ray_tpu.util import profiling

    text = step[1].as_text()
    relaid = {dims for dims in re.findall(
        r"f32\[((?:\d+,)*8,512(?:,\d+)*)\]", text) if dims.count(",") >= 3}
    assert not relaid, relaid
    writes = profiling.scope_writes(text, "ssm_gate_norm")
    assert writes["instructions"] <= 32, writes["instructions"]
    assert 0 < writes["bytes"] < 4.14e9, writes["bytes"]
    assert not [w for w in writes["writes"] if w["opcode"] == "copy"]


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("nemotron3nano_b1_s16384_bytes", nbytes)
    print(f"nemotron3nano-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # With the base set alone (no capacity handed down) XLA gives the step
    # 12,043,961,856 bytes and 3.1726e13 flops (PR 51's compile; under PR
    # 46's 12.46e9). The plan has room for every candidate: the four
    # Mamba-2 layers' input projections and gated norms' outputs, the four
    # expert layers' routing choices and shared up projections, 2.87 GB,
    # and XLA's figure stays a GiB under the chip's 15.75 (14,684,500,992
    # since the attention layer's lse is 4 bytes a row; 14,950,807,040).
    plan = cell.plan
    assert plan.layers_extended == 8 and plan.kept_extra_bytes == 2_865_234_176
    assert nbytes <= HBM_BYTES - 2 ** 30
    # PR 46's line still, on the step less what the plan added
    # (12,085,572,864: the gated norm's relayouts have not come back), and
    # the base set is the seventeen names' and a layer's input, no more
    assert nbytes - plan.kept_extra_bytes < 12.46e9
    assert plan.base_bytes <= 3_009_413_120
    # the four projections, 4 x 2 x 16384 x 2688 x 10304 = 3.63e12 flops,
    # are not made again (nor the shared experts': 1.31e12 more)
    flops = step[1].cost_analysis()["flops"]
    assert flops < 3.1726e13 - 3.63e12, flops
