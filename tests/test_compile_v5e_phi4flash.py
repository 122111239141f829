"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `phi4flash-train-1chip` cell as the cell runs it —
Phi-4-mini-flash-reasoning at its published widths (d 2560, 40 query heads
over 20 kv heads x 64, MLP 10,240, Mamba-1 5,120 channels x 16 states,
window 512), eight layers by the model's own rule (three Mamba-1, two
windowed, one full, one GMU, one cross), a quarter of the vocabulary,
B=1 x S=16384, remat on, the default optimizer — compiles for one chip,
calls exactly the attention and the selective-scan kernels under the
program's scopes, each forward once though remat is on, holds no array
with axes [S, 5120, 16], and fits the chip by XLA's memory analysis
(PERF.md §4 has the figure; it decides ISSUE 31's one fallback).
tests/compile_v5e.py has the described topology and the lowering."""

import math
import re

import pytest

from chipbench.families import sambay
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)

FALLBACK_OVER = 15.0e9          # ISSUE 31: over this, S = 8,192


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, sambay, "configs/phi-4-mini-flash-reasoning.json",
        "traffic/pretrain-phi4flash-b1-s16384.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.mamba_inner, cfg.mamba_d_state,
            cfg.dt_rank, cfg.sliding_window, cfg.vocab_size) == (
                8, 2560, 40, 20, 64, 10240, 5120, 16, 160, 512, 50016)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "selective_scan_fwd", "selective_scan_bwd")


def test_step_calls_exactly_the_attention_and_scan_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        sambay.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in SCOPES:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    # no row of the new kernels reads as one of Mamba-2's scan, which
    # layer_metrics/ssm_scan_ms_per_step.py matches as a substring
    assert not any("ssm_scan" in r for r in rows), rows


def test_no_forward_kernel_runs_twice_a_step(step):
    """Remat is on, and a block keeps what its kernels made
    (models/decoder.py KEPT_UNDER_REMAT): three Mamba-1 layers call the
    scan's forward kernel 3 times a step, not 6, and the four attention
    layers (two windowed, the full one, the cross one) their forward 4
    times: no score map is computed twice forward. The two windowed
    layers' calls carry `_window` since PR 65 (ops/attention.py); the
    cell's three kernel metrics match the kernel's name as a substring and
    read all four."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1].as_text()) == {
        "selective_scan_fwd": 3, "selective_scan_bwd": 3,
        "flash_attention_fwd": 2, "flash_attention_dq": 2,
        "flash_attention_dkv": 2, "flash_attention_fwd_window": 2,
        "flash_attention_dq_window": 2, "flash_attention_dkv_window": 2}


def test_step_holds_no_state_a_token(step):
    """The scan's state lives in VMEM, a token at a time, in both passes:
    no buffer of the step has the axes [16384, 5120, 16] or [16384, 16,
    5120] in any tiling (a plain XLA scan or associative_scan would hold
    float32 [1, 16384, 5120, 16], 5.4 GB a layer)."""
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    sizes = [[int(n) for n in dims.split(",")]
             for dims in re.findall(r"\[((?:\d+,)*\d+)\]", entry)]
    per_token = 16384 * 5120 * 16
    assert not [s for s in sizes if math.prod(s) >= per_token
                and 16384 in s], "an array as large as a state a token"
    # what it does hold: x and m of a Mamba-1 layer as the kernels take
    # them, and one float32 state a chunk of 64 tokens
    assert re.search(r"f32\[1,16384,40,128\]", entry)
    assert re.search(r"f32\[1,256,16,40,128\]", entry)


def test_the_convolution_writes_no_tap_scaled_copies(step):
    """The causal depthwise convolution of three Mamba-1 layers at
    [1, 16384, 5120] (168 MB a crossing), ops/layers.py
    `causal_conv1d_silu`. `profiling.scope_writes` over `ssm_conv` reads
    18 instructions and 0.50 GB here, as it did while autodiff derived the
    backward pass (PR 33's tree): XLA folded that pass into a fusion named
    after a neighbour outside the scope, so the scope's own rows never
    held it. What did show it is what the whole step writes at that size:
    101 arrays of 168 MB, five of them from each of three fusions (the
    cotangent times each tap, and g). With the rule: 89, four fewer a
    layer, and no instruction writes more than two."""
    from ray_tpu.util import profiling

    text = step[1].as_text()
    got = profiling.scope_writes(text, "ssm_conv")
    print(f"ssm_conv: {got['instructions']} instructions, "
          f"{got['bytes'] / 1e9:.2f} GB a step")
    crossing = 16384 * 5120 * 2
    assert got["instructions"] <= 18 and got["bytes"] < 0.51e9
    assert max(sum(r >= crossing for r in w["results"])
               for w in got["writes"]) <= 2
    # every instruction of the step, whatever scope names it
    everywhere = profiling.scope_writes(text, "")["writes"]
    wide = [sum(r == crossing for r in w["results"]) for w in everywhere]
    assert max(wide) <= 2
    assert sum(wide) <= 89


def test_windowed_layers_compute_the_band_and_nothing_else():
    """What the windowed layers' kernels run at the cell's shape, by the
    plan the kernels take their loops from (the step above compiled with
    it): own blocks of 1,024 as the full layers', 16 programs a head, a
    program four strips of 256 own positions, a strip one tile of 768 of
    the other side (the far edge's sub-block and the diagonal's masked,
    the one between whole): 189 of the 4,096 sub-blocks in the square a
    head, where the causal triangle alone is 2,080, in 65 tiles (69 in
    dK/dV until PR 58, while its queries came in two grid blocks of 8,192
    beside lse and delta padded to 128 lanes; they are resident now) where
    own blocks of one sub-block ran them as 189 tiles of 64 programs."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import VMEM_BUDGET, attention_plan

    band = attention_plan(16384, 64, True, jnp.bfloat16, window=512,
                          v_dim=128)
    causal = attention_plan(16384, 64, True, jnp.bfloat16, v_dim=128)
    for kernel, swept, tiles in ((band.fwd, 16384, 65), (band.dq, 16384, 65),
                                 (band.dkv, 16384, 65)):
        assert (kernel.block, kernel.swept, kernel.sub) == (1024, swept, 256)
        assert (kernel.computed, kernel.skipped) == (189, 4096 - 189)
        assert 126 <= kernel.masked <= 129
        assert kernel.tiles == tiles <= 16384 // 256 + 8
        assert kernel.vmem_bytes <= VMEM_BUDGET
    assert causal.fwd.computed == 2080
    assert band.executed_share < causal.executed_share / 10


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("phi4flash_b1_s16384_bytes", nbytes)
    print(f"phi4flash-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # With the base set alone XLA gives the step 14,254,285,824 bytes (PR
    # 51's compile, as since PR 37), under ISSUE 31's line for its one
    # fallback, so the cell stays at 16,384. What that leaves held the
    # first layer's two projections and the second Mamba-1 layer's input
    # projection (1.34 GB); since PR 58 the four attention layers' lse is 4
    # bytes a row (1.33 GB less in the base set) and it holds the first
    # three layers' gate and up and both Mamba-1 layers' input projections
    # (2.68 GB), and XLA's figure stays a GiB under the chip's as it was
    # (15,594,413,568: 15.59 GB, 14.52 GiB).
    plan = cell.plan
    assert plan.extras == (("mlp_gate_up", "ssm_in_proj"), ("mlp_gate_up",),
                           ("mlp_gate_up", "ssm_in_proj")) + ((),) * 5
    assert plan.kept_extra_bytes == 2_684_354_560
    assert nbytes - plan.kept_extra_bytes < FALLBACK_OVER
    assert nbytes <= HBM_BYTES - 2 ** 30
    # PR 34's and PR 37's line still, on the step less what the plan added
    # (14,252,511,744): no residual joined the base set's step with the
    # convolution's rule, nor with the band's large own blocks (VMEM, not
    # HBM); and the base set is the seventeen names' and a layer's input
    assert nbytes - plan.kept_extra_bytes <= 14_254_285_824
    assert plan.base_bytes <= 3_449_815_040
