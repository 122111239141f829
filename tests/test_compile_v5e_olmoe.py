"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `olmoe-train-1chip` cell as the cell runs it — OLMoE-1B-7B
at its published widths (d 2048, 16 heads of 128, 64 experts of 1024, 8 a
token, V 50,304), depth 2, B=4 x S=4096, remat on, the default optimizer —
compiles for one chip, calls the attention and the grouped-matmul kernels
under the program's scopes, each forward once though remat is on (the
blocks keep what the kernels made), gathers the T*k rows eight times and
copies none of them to keep it, holds no [T, E, C] dispatch tensor and
no float32 copy of an expert tensor, and fits the chip by XLA's memory
analysis (PERF.md §4 has the figure).
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import olmoe
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, olmoe, "configs/olmoe-1b-7b.json",
        "traffic/pretrain-olmoe-b4-s4096.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.d_expert,
            cfg.experts_per_token) == (2, 2048, 64, 1024, 8)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


def test_step_calls_the_attention_and_grouped_matmul_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        olmoe.MOSAIC_KERNELS)
    # The trace names a kernel by its HLO instruction: every Mosaic call
    # of the compiled step carries one of the program's scopes.
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    scopes = ("flash_attention_fwd", "flash_attention_dq",
              "flash_attention_dkv", "grouped_matmul_fwd",
              "grouped_matmul_dlhs", "grouped_matmul_drhs")
    assert all(s in profiling.DEVICE_SCOPES for s in scopes)
    for scope in scopes:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in scopes) for r in rows), rows


def _row_gathers(text):
    """(gathers that make a bf16[131072, 2048] value, those of them whose
    operand has 131,072 rows) in a compiled program's text."""
    shape = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    operands = re.findall(
        r"= bf16\[131072,2048\]\S* gather\(%([\w.\-]+),", text)
    return len(operands), sum(
        shape[name].startswith("bf16[131072,") for name in operands)


def test_no_forward_kernel_runs_twice_a_step(step):
    """Remat is on in this cell, and a block keeps what its kernels and
    its row dispatch made (models/decoder.py KEPT_UNDER_REMAT): per layer
    three grouped matmuls and one attention, each forward once. While the
    blocks kept nothing (until PR 28) the counter read grouped_matmul_fwd
    12 and flash_attention_fwd 4, eight forward calls run twice."""
    from ray_tpu.util import profiling

    text = step[1].as_text()
    calls = profiling.kernel_calls(text)
    assert calls == {
        "grouped_matmul_fwd": 6, "grouped_matmul_dlhs": 6,
        "grouped_matmul_drhs": 6, "flash_attention_fwd": 2,
        "flash_attention_dq": 2, "flash_attention_dkv": 2}
    # forward kernels run twice: 0 (6 + 2 before)
    assert (calls["grouped_matmul_fwd"] - calls["grouped_matmul_dlhs"]
            + calls["flash_attention_fwd"] - calls["flash_attention_dq"]) == 0
    # Gathers that make the T*k rows, and those of them whose operand has
    # T*k rows too (4.5 ms each on the chip; from the T tokens' rows 0.83
    # to 4.3): a layer's dispatch and its rows back in token order, and in
    # the backward pass the output's cotangent spread from the tokens' rows
    # and the dispatch's cotangent brought back. The routed experts' one
    # gradient rule (parallel/moe.py `_experts`) gathers no [T, k, d]
    # product, and `moe_xs` is kept, so the dispatch is not made again:
    # 8 and 4 (10 and 6 before PR 30, 12 while the blocks kept nothing).
    made, from_rows = _row_gathers(text)
    assert made <= 8 and from_rows <= 4, (made, from_rows)
    # A kept value the forward pass reads too gets a `reduce_precision`
    # from jax.checkpoint, on the chip a plain copy of it; the experts'
    # kept values are residuals of their rule and of nothing else. (12
    # such lines before PR 30: gate, up and the unsorted rows of each
    # layer, in a fused computation and at its call.)
    assert not re.findall(
        r"= \w+\[131072,\d+\]\S* reduce-precision\(", text)


def test_step_holds_no_dispatch_tensor_and_no_float32_expert_copy(step):
    text = step[1].as_text()
    # top2_gating's [T, E, C] at this shape would be [16384, 64, 2560].
    assert not re.search(r"\[16384,64,\d{3,}\]", text)
    # Buffers are the entry computation's values (the optimizer's fused
    # bodies, printed before it, work on float32 elements in registers).
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"f32\[64,2048,1024\]|f32\[64,1024,2048\]", entry)
    assert re.search(r"bf16\[64,2048,1024\]", entry)
    assert re.search(r"bf16\[131072,2048\]", entry)     # T*k rows, bf16


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("olmoe_b4_s4096_bytes", nbytes)
    # 11.25 GB (11,253,872,640) since PR 58: two layers' lse and delta at 4
    # bytes a row where they were padded to 128 lanes. 11.71 GB since PR 30
    # (11.69 before it: `moe_xs` is kept where the unsorted rows were); the
    # runtime's peak on the chip is in PERF.md §2.
    print(f"olmoe-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # the routed experts' layers have no candidate of the second table
    plan = cell.plan
    assert plan.extras == ((), ()) and plan.kept_extra_bytes == 0
    assert nbytes <= HBM_BYTES - 2 ** 30
