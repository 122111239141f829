"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `olmohybrid-train-1chip` cell as the cell runs it —
Olmo-Hybrid-7B at its published widths (d 3840, 30 heads x 128, MLP
11,008, linear attention 30 heads of 96 x 192 over 11,520 convolved
channels), the first period of four layers (three linear_attention, one
full_attention), a quarter of the vocabulary, B=1 x S=16384, remat on, the
default optimizer — compiles for one chip, calls exactly the attention and
the gated-delta-rule kernels under the program's scopes, each forward once
though remat is on, holds no state a token, and fits the chip by XLA's
memory analysis (PERF.md §4 has the figure; it decides ISSUE 41's one
lever, the vocabulary). And the four families the benchmark already had
trace to the kernel calls and the number of equations they had on the
parent of PR 41: the block's new norm placement and the convolution with
no bias changed nothing for them.
tests/compile_v5e.py has the described topology and the lowering."""

import collections
import math
import re

import pytest

from chipbench.families import olmo_hybrid
from compile_v5e import (HBM_BYTES, lowered_cell_step, topo,  # noqa: F401
                         total)

LEVER_OVER = 15.0e9             # ISSUE 41: over this, vocab_size 12,544


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, olmo_hybrid, "configs/olmo-hybrid-7b.json",
        "traffic/pretrain-olmohybrid-b1-s16384.json")
    cfg = lowered.cfg
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
            cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_dim,
            cfg.linear_chunk_size, cfg.vocab_size) == (
                4, 3840, 30, 128, 11008, 30, 96, 192, 11520, 64, 25088)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered, compiled) train step of the cell on one described chip."""
    return cell.lowered, cell.lowered.compile()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "gated_delta_fwd", "gated_delta_bwd")


def test_step_calls_exactly_the_attention_and_delta_rule_kernels(step):
    from chipbench import harness, xplane
    from ray_tpu.util import profiling

    lowered, compiled = step
    assert harness.mosaic_kernel_names(lowered.as_text()) == set(
        olmo_hybrid.MOSAIC_KERNELS)
    rows = {xplane.short_name(line.strip())
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " = " in line}
    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    for scope in SCOPES:
        assert any(scope in r for r in rows), (scope, rows)
    assert all(any(s in r for s in SCOPES) for r in rows), rows
    # no row of the new kernels reads as another family's, which the
    # per-layer readers match as substrings
    for other in ("ssm_scan", "selective_scan", "grouped_matmul"):
        assert not any(other in r for r in rows), (other, rows)
    assert not any("flash_attention" in r and "gated_delta" in r
                   for r in rows)


def test_no_forward_kernel_runs_twice_a_step(step):
    """Remat is on, and a block keeps what its kernels made
    (models/decoder.py KEPT_UNDER_REMAT): three linear-attention layers
    call the rule's forward kernel 3 times a step, not 6, and the full
    layer its attention forward once."""
    from ray_tpu.util import profiling

    calls = profiling.kernel_calls(step[1].as_text())
    assert calls["gated_delta_fwd"] % 3 == 0
    assert calls["gated_delta_bwd"] % 3 == 0
    assert calls == {
        "gated_delta_fwd": 3, "gated_delta_bwd": 3,
        "flash_attention_fwd": 1, "flash_attention_dq": 1,
        "flash_attention_dkv": 1}


def test_step_holds_no_state_a_token(step):
    """The rule's state lives in VMEM from chunk to chunk, in both passes:
    no buffer of the step is as large as a [16384, 30, 96, 192] state a
    token (36 GB in float32: it could not be); what it does hold is one
    float32 state a chunk of 64 tokens, q | k | v in the projection's own
    layout, and no copy of them with the heads on an axis of their own
    ahead of the sequence."""
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    sizes = [[int(n) for n in dims.split(",")]
             for dims in re.findall(r"\[((?:\d+,)*\d+)\]", entry)]
    assert not [s for s in sizes if math.prod(s) >= 16384 * 30 * 96 * 192]
    assert re.search(r"f32\[1,256,30,96,192\]", entry)
    assert re.search(r"bf16\[1,16384,5760\]", entry)
    assert not re.search(r"bf16\[1,30,16384,(96|192)\]", entry)


def test_step_keeps_each_layers_inverse_once_at_its_own_size(step):
    """Each forward kernel call leaves its layer's T - I, a head and chunk
    64 x 64 bfloat16, with the heads side by side along the lanes: 1,920
    columns are 15 whole 128-lane tiles and 64 rows four 16-row ones, so
    HBM holds the 63 MB `kept_bytes` counts; a last axis of 64 would be
    padded to twice that. Each backward call takes one, and the step holds
    no other array of that many values or of that shape doubled."""
    from ray_tpu.ops.gated_delta import gated_delta_plan

    kept = "bf16[1,256,64,1920]"
    assert 256 * 64 * 1920 * 2 == gated_delta_plan(
        16384, 30, 96, 192, 64).kept_bytes == 62_914_560
    text = step[1].as_text()
    entry = text[text.index("\nENTRY "):]
    calls = [line.strip() for line in entry.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    made = [c for c in calls if c.startswith("%gated_delta_fwd")]
    read = [c for c in calls if c.startswith("%gated_delta_bwd")]
    assert len(made) == len(read) == 3
    for call in made:       # among the results, tiled and not padded
        results = call.split(" custom-call(")[0]
        assert results.count(kept + "{3,2,1,0:T(8,128)(2,1)}") == 1, results
    for call in read:
        operands = call.split("operand_layout_constraints={")[1].split(
            "}}")[0]
        assert operands.count(kept) == 1, operands
    sizes = {tuple(int(n) for n in dims.split(","))
             for dims in re.findall(r"bf16\[((?:\d+,)*\d+)\]", entry)}
    values = 256 * 30 * 64 * 64
    assert [s for s in sizes if math.prod(s) == values] == [(1, 256, 64,
                                                             1920)]
    assert not [s for s in sizes
                if s[-1] == 64 and math.prod(s) >= values]
    assert not [s for s in sizes         # a chunk's, padded to twice
                if math.prod(s) == 2 * values and s[:2] == (1, 256)]


def test_plan_counts_what_the_kernels_loop_over():
    from ray_tpu.ops.gated_delta import VMEM_LIMIT, gated_delta_plan

    plan = gated_delta_plan(16384, 30, 96, 192, 64)
    assert (plan.chunks, plan.heads_per_block, plan.grid) == (256, 30, (256,))
    assert (plan.key_tile, plan.value_tile) == (128, 256)
    assert plan.state_bytes == 256 * 30 * 96 * 192 * 4
    # the forward makes T for two heads a 128-lane tile, ten float32
    # products a pair and eight bfloat16 ones a head; the backward reads
    # it: 21 products a head and chunk
    assert plan.heads_per_tile == 2
    assert plan.inverse_matmuls == 256 * 15 * 10 == 38_400
    assert (plan.fwd_matmuls, plan.bwd_matmuls) == (99_840, 161_280)
    assert (plan.fwd_exps, plan.bwd_exps) == (3_840, 7_680)
    assert plan.vmem_bytes <= VMEM_LIMIT


def test_step_fits_a_chip(step, cell, record_property):
    mem = step[1].memory_analysis()
    nbytes = total(mem)
    record_property("olmohybrid_b1_s16384_bytes", nbytes)
    print(f"olmohybrid-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    # With the base set alone XLA gives the step 13,649,982,976 bytes (PR
    # 51's compile), under ISSUE 41's line for its one lever, so the
    # vocabulary stays a quarter. What that leaves holds the first layer's
    # projections (q | k | v, the gate, the MLP's gate and up: 1.29 GB) and,
    # since PR 58 (the attention layer's lse at 4 bytes a row: 0.25 GB less
    # in the base set), the second layer's q | k | v and gate (0.57 GB), and
    # XLA's figure stays a GiB under the chip's (15,064,627,200;
    # 14,937,150,464 under PR 51's plan).
    plan = cell.plan
    assert plan.extras == (("gated_delta_in", "mlp_gate_up"),
                           ("gated_delta_in",), (), ())
    assert nbytes - plan.kept_extra_bytes < LEVER_OVER
    assert plan.base_bytes <= 3_462_266_880
    assert nbytes <= HBM_BYTES - 2 ** 30


# The kernel calls and the equations of each accepted family's loss
# gradient at its tiny size, traced with the kernels in it, as the parent
# of PR 41 (08fdd84) traced them: `_block` reads a layer's norms off its
# weights now and the convolution takes no bias, and for a layer with
# `ln1`, `ln2` and a bias both are the code they were. Since PR 51 the
# projections a block may keep have names (models/decoder.py
# KEPT_WHERE_IT_FITS): one `name` equation each, forward and made again, 20
# in hybrid's gradient and 22 in sambay's, which lower to nothing. Since PR
# 58 the attention kernels' lse and delta are lane rows of four bytes a
# position (ops/attention.py): the relayouts between a column and a lane
# row inside the kernels, where the broadcasts to 128 lanes were (the
# forward's a mask and a sum over sublanes a 128 rows, `_lane_row`; dQ's
# two transposes), are 20 equations more a forward kernel that saves lse
# at these 128 positions, and 11, 11 and 12 more an attention layer in
# hybrid's, moe's and sambay's gradients. Since PR 62 the values q, k and v
# are made from where a layer holds `wq` and `wkv` apart have names (a
# sparse block's candidates, models/decoder.py FITS_BY_KIND): three `name`
# equations in hybrid's one attention layer, which lower to nothing.
PARENT = {
    "gpt": {"_eqns": 1115 + 40, "flash_attention_fwd": 4,
            "flash_attention_dq": 2, "flash_attention_dkv": 2},
    "moe": {"_eqns": 3693 + 22, "flash_attention_fwd": 2,
            "flash_attention_dq": 2,
            "flash_attention_dkv": 2, "grouped_matmul_fwd": 6,
            "grouped_matmul_dlhs": 6, "grouped_matmul_drhs": 6},
    "hybrid": {"_eqns": 2065 + 20 + 11 + 3, "flash_attention_fwd": 1,
               "flash_attention_dq": 1, "flash_attention_dkv": 1},
    "sambay": {"_eqns": 4921 + 22 + 48, "flash_attention_fwd": 4,
               "flash_attention_dq": 4, "flash_attention_dkv": 4,
               "selective_scan_fwd": 3, "selective_scan_bwd": 3},
}


def _count(jaxpr, counts):
    import jax

    from ray_tpu.util import profiling

    for eqn in jaxpr.eqns:
        counts["_eqns"] += 1
        if eqn.primitive.name == "pallas_call":
            scope = str(eqn.source_info.name_stack).split("/")[-1]
            counts[next(s for s in profiling.DEVICE_SCOPES
                        if s in scope)] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, counts)
    return counts


@pytest.mark.parametrize("family", sorted(PARENT))
def test_accepted_families_trace_to_what_the_parent_traced(family,
                                                           monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.models.sambay import SambaYConfig
    from ray_tpu.ops import attention

    cfg, init, loss = {
        "gpt": (models.GPTConfig.tiny(), models.gpt_init, models.gpt_loss),
        "moe": (models.MoEConfig.tiny(), models.moe_init, models.moe_loss),
        "hybrid": (models.HybridConfig.tiny(), models.hybrid_init,
                   models.hybrid_loss),
        "sambay": (SambaYConfig.tiny(), models.sambay_init,
                   models.sambay_loss)}[family]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    grad = jax.make_jaxpr(jax.grad(
        lambda p, t: loss(p, (t, t), cfg)))(params, tok)
    assert dict(_count(grad.jaxpr, collections.Counter())) == PARENT[family]
