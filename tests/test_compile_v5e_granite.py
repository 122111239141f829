"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `granite4h-train-1chip` cell as the cell runs it —
granite-4.0-h-micro at its published widths (d 2048, Mamba-2 64 heads x 64
with state 128, attention 32 query heads over 8 kv heads x 64, MLP 8192,
V 100,352), the first period of ten layers (nine Mamba-2, one attention),
B=1 x S=16384, remat on, the default optimizer — compiles for one chip,
calls exactly the attention and the scan kernels under the program's
scopes, each forward once though remat is on, holds no float32 array with
two chunk-long axes a head, and fits the chip by XLA's memory analysis
(PERF.md §4 has the figure).
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import granite_hybrid
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, granite_hybrid, "configs/granite-4.0-h-micro.json",
        "traffic/pretrain-granite4h-b1-s16384.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.d_model, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_d_state, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size) == (10, 2048, 64, 64, 128, 32, 8, 8192, 100352)
    assert cfg.layer_types.count("attention") == 1
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


def test_step_calls_exactly_the_attention_and_scan_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        granite_hybrid.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    scopes = ("flash_attention_fwd", "flash_attention_dq",
              "flash_attention_dkv", "ssm_scan_fwd", "ssm_scan_bwd")
    assert all(s in profiling.DEVICE_SCOPES for s in scopes)
    for scope in scopes:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in scopes) for r in rows), rows


def test_no_forward_kernel_runs_twice_a_step(step):
    """Remat is on, and a block keeps what its kernels made
    (models/decoder.py KEPT_UNDER_REMAT): nine Mamba-2 layers call the
    scan's forward kernel 9 times a step, not 18, and the one attention
    layer its forward once."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1].as_text()) == {
        "ssm_scan_fwd": 9, "ssm_scan_bwd": 9, "flash_attention_fwd": 1,
        "flash_attention_dq": 1, "flash_attention_dkv": 1}


def test_step_holds_no_array_with_two_chunk_long_axes_a_head(step):
    """The [Q, Q] decay and score tiles stay in VMEM, in both passes: no
    buffer of the step has two 256-long axes, in any dtype (a plain XLA
    chunked scan would hold float32 [1, 64, 256, 256, 64] ones)."""
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"\[(?:\d+,)*256,(?:\d+,)*256(?:,\d+)*\]", entry)
    # what it does hold: x and y of a Mamba-2 layer in the projection's
    # own layout, and one float32 state a chunk and pair of heads
    assert re.search(r"bf16\[1,16384,4096\]", entry)
    assert re.search(r"f32\[1,64,32,128,128\]", entry)


def test_the_convolution_writes_half_of_what_autodiff_made_it_write(step):
    """`profiling.scope_writes` over `ssm_conv`, the causal depthwise
    convolution and its silu in nine Mamba-2 layers at [1, 16384, 4352]
    (143 MB a crossing). With its backward pass derived by autodiff the
    step read 108 instructions and 11.55 GB (PR 33's tree): a layer's
    backward wrote the cotangent times each tap as four arrays, 570 MB in
    one fusion, and read them back through four pads. With the rule of
    ops/layers.py `causal_conv1d_silu` it reads 90 and 6.42 GB: a layer
    writes y forward (143 MB), the pre-activation and y made again under
    remat (285: XLA keeps the first for the backward pass rather than make
    it a third time, which the chip runs 1.6 ms a step faster than a step
    forced to, PERF.md section 6), then g with the taps' and the bias's
    gradients as column sums of the same pass (143) and dx (143)."""
    from ray_tpu.util import profiling

    got = profiling.scope_writes(step[1].as_text(), "ssm_conv")
    print(f"ssm_conv: {got['instructions']} instructions, "
          f"{got['bytes'] / 1e9:.2f} GB a step")
    assert got["instructions"] <= 90 and got["bytes"] < 6.5e9
    crossing = 16384 * 4352 * 2
    large = [[r for r in w["results"] if r >= crossing]
             for w in got["writes"] if max(w["results"]) >= crossing]
    # four passes a layer, five sequence-sized arrays, never more than two
    # from one instruction
    assert len(large) == 9 * 4 and max(len(r) for r in large) == 2
    assert sum(sum(r) for r in large) == 9 * 5 * crossing
    # the pass that makes g makes the five column sums too, and nothing
    # sequence-sized beside g
    sums = [w for w in got["writes"] if len(w["results"]) == 6]
    assert len(sums) == 9
    assert all(sorted(w["results"])[:5] == [4352 * 2] * 5 for w in sums)


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("granite4h_b1_s16384_bytes", nbytes)
    print(f"granite4h-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # With the base set alone XLA gives the step 14,398,392,320 bytes (PR
    # 51's compile; PR 34's line was 14,473,369,600), 13.41 GiB of 15.75:
    # what that leaves above a GiB holds the first layer's candidates and,
    # since PR 58 (the attention layer's lse at 4 bytes a row: 0.27 GB less
    # in the base set), the second's gate and up where its input projection
    # was, 1.49 GB (all nine layers' input projections alone would stand at
    # 15.56 GiB), and XLA's figure stays a GiB under the chip's
    # (15,618,870,272; 15,630,291,968 under PR 51's plan).
    plan = cell.plan
    assert plan.extras == (("mlp_gate_up", "ssm_gated", "ssm_in_proj"),
                           ("mlp_gate_up",)) + ((),) * 8
    assert nbytes <= HBM_BYTES - 2 ** 30
    # PR 34's line still, on the step less what the plan added: no residual
    # joined the base set's step with the convolution's rule (a kept value
    # costs XLA its bytes here: 14,131,989,504 left), and the base set is
    # the seventeen names' and a layer's input, no more
    assert nbytes - plan.kept_extra_bytes <= 14_473_369_600
    assert plan.base_bytes <= 3_357_540_352
