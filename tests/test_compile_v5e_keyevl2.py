"""Compile-only, against a described v5e:2x2 (no chip, no timings): the
train step of the `keyevl2-train-1chip` cell as the cell runs it —
Keye-VL-2.0-30B-A3B's decoder at its published widths (d 2048, 32 heads of
128 over 4, an indexer of 16 heads of 64 naming 2,048 keys a query, a
128-wide softmax router over 16 held SwiGLU experts of 768, V 18,992
untied), six layers, B=1 x S=16384, remat on, AdamW at the family's rate —
compiles for one chip, calls exactly the attention, indexer and
grouped-matmul kernels under the program's scopes, each of them once a
layer though remat is on (the indexer's backward kernel in the FORWARD
pass), hands the three attention kernels a tile of the [16384, 16384] int8
selection on the grid `attention_plan` gives under one, never holds a
[16, 16384, 16384] or [32, 16384, 16384] map, and fits the chip by XLA's
memory analysis with `remat_plan`'s reserve counting the [S, S] buffers
(PERF.md section 4 has the figures). The XLA compile is about a minute of
one worker and stays in tier-1.
tests/compile_v5e.py has the described topology and the lowering."""

import re

import pytest

from chipbench.families import keye_vl2 as family
from compile_v5e import (HBM_BYTES, lowered_cell_step,  # noqa: F401
                         assert_flash_rows_are_lane_rows, mosaic_call_types,
                         mosaic_grids, topo, total)


@pytest.fixture(scope="module")
def cell(topo):
    """The cell's train step lowered for one described chip, its
    configuration at the published widths."""
    lowered = lowered_cell_step(
        topo, family, "configs/keye-vl-2.0-30b-a3b.json",
        "traffic/pretrain-keyevl2-b1-s16384.json")
    cfg, mix = lowered.cfg, lowered.mix
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.index_heads, cfg.index_head_dim,
            cfg.index_topk, cfg.n_experts, cfg.held, cfg.experts_per_token,
            cfg.d_expert, cfg.vocab_size, cfg.router_aux_loss_coef,
            cfg.index_loss_weight) == (
        6, 2048, 32, 4, 128, 16, 64, 2048, 128, (0, 16), 8, 768, 18992,
        0.001, 1.0)
    assert (mix["global_batch"], mix["seq"], mix["ring_batches"]) == (
        1, 16384, 1)
    return lowered


@pytest.fixture(scope="module")
def step(cell):
    """(lowered text, compiled text, XLA's memory analysis) of that step."""
    compiled = cell.lowered.compile()
    return cell.lowered.as_text(), compiled.as_text(), \
        compiled.memory_analysis()


SCOPES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
          "sparse_index_fwd", "sparse_index_bwd", "grouped_matmul_fwd",
          "grouped_matmul_dlhs", "grouped_matmul_drhs")


def test_lowered_step_hands_the_kernels_the_selection_and_holds_no_map(cell):
    """Before XLA: the step's Mosaic kernels are the family's seven; each
    of the three attention kernels takes q, k and v [32, 16384, 128] and
    one [1, 16384, 16384] int8 selection; the indexer's forward kernel
    writes one [1, 16384, 16384] float32 and its backward reads one; and no
    value is a map a head."""
    from chipbench import harness

    lowered = cell.lowered.as_text()
    assert harness.mosaic_kernel_names(lowered) == set(family.MOSAIC_KERNELS)
    for name, types in mosaic_call_types(
            lowered, ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")):
        assert types.count("<1x16384x16384xi8>") == 1, (name, types)
        assert len(re.findall(r"<32x16384x128xbf16>", types)) >= 4, name
    (_, fwd), = mosaic_call_types(lowered, ("_index_fwd_kernel",))
    (_, bwd), = mosaic_call_types(lowered, ("_index_bwd_kernel",))
    for types in (fwd, bwd):
        assert types.count("<1x16384x16384xf32>") == 1
        assert "<1x16x16384x64xbf16>" in types and "<1x16384x64xbf16>" in types
    assert not re.search(r"(16|32)x16384x16384", lowered)


def test_the_kernels_run_the_grids_the_plans_say(cell):
    """`attention_plan(16384, 128, selected=2048)`: all three hold 1,024
    own positions against the other side in FOUR grid blocks of 4,096 (K
    and V were whole, dK/dV's queries in two of 8,192, without a selection:
    its [1024, 16384] int8 tile twice would be the whole VMEM budget), and
    a [1024, 4096] tile of the selection beside them; the indexer's two run
    32 x 32 tiles of 512."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_plan
    from ray_tpu.ops.sparse_index import sparse_index_plan

    plan = attention_plan(16384, 128, True, jnp.bfloat16, None, 128, 2048)
    for kernel in (plan.fwd, plan.dq, plan.dkv):
        assert (kernel.block, kernel.swept, kernel.tiles) == (1024, 4096, 184)
    assert (plan.executed_pairs, plan.required_pairs) == (
        136_314_880, 31_458_304)
    lowered = cell.lowered.as_text()
    grids = mosaic_grids(lowered, ("_fwd_kernel", "_dq_kernel",
                                   "_dkv_kernel", "_index_fwd_kernel",
                                   "_index_bwd_kernel"))
    own, swept, row = (1, 1024, 128), (1, 4096, 128), (1, 1, 1024)
    tile = (1, 1024, 4096)
    assert grids["_fwd_kernel"] == {((32, 16, 4), (own, swept, swept, tile,
                                                   own, row))}
    (grid, blocks), = grids["_dq_kernel"]
    assert grid == (32, 16, 4)
    assert blocks[:7] == (own, swept, swept, own, row, row, tile)
    (grid, blocks), = grids["_dkv_kernel"]
    assert grid == (32, 16, 4)
    assert blocks[:7] == (swept, own, own, swept, (1, 1, 4096), (1, 1, 4096),
                          tile)
    index = sparse_index_plan(16384, 16, 64)
    assert (index.tile, index.grid) == (512, 32)
    # the two plain passes' (query, key) pairs: a chunk of each against
    # its band's key prefix, four bands of 4,096 queries, 5/8 of the square
    # (ISSUE 61 asked 0.57; PERF.md section 6 has why the passes keep four)
    assert index.select_pairs == index.target_pairs == 167_772_160 \
        == 5 * 16384 ** 2 // 8
    q, k, w, square = (1, 16, 512, 64), (1, 512, 64), (1, 512, 16), \
        (1, 512, 512)
    assert grids["_index_fwd_kernel"] == {((1, 32, 32), (q, k, w, square))}
    (grid, blocks), = grids["_index_bwd_kernel"]
    assert grid == (1, 32, 32)
    assert blocks[:7] == (q, k, w, square, q, (1, 1, 512, 64), (1, 512, 128))


def test_the_kernels_rows_are_four_bytes_a_position(cell):
    """lse and delta stay lane rows [32, 1, 16384] under a selection."""
    assert_flash_rows_are_lane_rows(cell.lowered.as_text(), 32)


def test_the_plan_counts_the_square_buffers(cell):
    """`remat_plan` as the step was traced with a chip's 15.75 GiB. State
    5.29 GB; the base set 3.10: a block keeps its input, the kernel's output
    and lse, the SELECTION (268 MB) and the indexer's three gradients (34
    MB) (KEPT_BY_KIND); the reserve counts six bytes a (query, key) pair,
    1.61 GB, beside the block's named values (`_selection_holds`); what is
    left holds, in all six blocks, q, the two projections q, k and v are
    made from, k normed and rotated at kv-head width, the branch's output
    (FITS_BY_KIND) and the routing's choices, 2.32 GB, with 1.7 GB to
    spare. k's and v's copies across a group are made again."""
    plan = cell.plan
    assert 5.28e9 < plan.state_bytes < 5.30e9
    assert plan.base_bytes == 3_101_692_416
    selections = 6 * 16384 * 16384
    assert plan.base_bytes > selections
    from ray_tpu.ops.loss import working_set_bytes
    loss = working_set_bytes(16384, 2048, 18992)
    assert plan.reserve_bytes == 3_458_728_768 > 6 * 16384 ** 2 + loss
    assert plan.extras == (("attention_k_heads", "attention_kv_proj",
                            "attention_q_proj", "flash_attention_q",
                            "moe_choice", "sparse_attention_out"),) * 6
    # a layer: q and its projection [16384, 4096], k | v [16384, 1024], k
    # [4, 16384, 128], the output [16384, 2048], bfloat16 all, and the choices
    assert plan.kept_extra_bytes == 6 * (
        2 * 134_217_728 + 33_554_432 + 16_777_216 + 67_108_864
        + 1_572_928) == 2_324_693_376
    assert plan.layers_extended == 6
    assert plan.bytes_left == 1_666_242_872


def test_step_calls_exactly_the_seven_kernels_under_the_programs_scopes(step):
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
    assert set(family.SPARSE_INDEX_SCOPES) <= set(SCOPES)
    # the indexer's passes reach the compiled step's instructions inside
    # the branch's scope
    for scope in ("sparse_index_proj", "sparse_select", "sparse_target"):
        assert scope in profiling.DEVICE_SCOPES
        assert f"/sparse_attention_mixer/{scope}/" in compiled, scope
    assert "/channel_mixer/moe_route/" in compiled


def test_no_kernel_runs_twice_though_remat_is_on(step):
    """A sparse block keeps the selection, attention's output and lse and
    the indexer's three gradients: each of the six blocks calls the
    attention forward, the indexer's forward and the indexer's BACKWARD
    kernel once (the last in the forward pass: `indexer_loss`'s rule), and
    scores and selects once. The six expert layers call their two forward
    grouped matmuls once and make the first again in the backward rule
    (lfm2moe-train-1chip's counts a layer)."""
    from ray_tpu.util import profiling

    assert profiling.kernel_calls(step[1]) == {
        "flash_attention_fwd": 6, "flash_attention_dq": 6,
        "flash_attention_dkv": 6, "sparse_index_fwd": 6,
        "sparse_index_bwd": 6, "grouped_matmul_fwd": 18,
        "grouped_matmul_dlhs": 12, "grouped_matmul_drhs": 12}
    assert not re.search(r"\[(1,)?(16|32),16384,16384\]", step[1])


def test_the_plain_passes_loop_a_band_over_key_prefixes(step):
    """`select` runs four loops a layer (each with its bisection's loop
    of 32 counts inside) and `index_target` four, each under its scope,
    and a loop's chunk reads its band's key prefix: the bisection's counts
    compare [512, extent] tiles and the target's products are [.., 256,
    extent], extent the band's last query; only the last band's are the
    whole row."""
    compiled = step[1]
    for scope, chunk, extents, nested in (
            ("sparse_select", 512, range(4096, 16385, 4096), 2),
            ("sparse_target", 256, range(4096, 16385, 4096), 1)):
        lines = [line for line in compiled.splitlines()
                 if f"/sparse_attention_mixer/{scope}/" in line]
        whiles = [line for line in lines if re.search(r" while\(", line)]
        assert len(whiles) == 6 * len(extents) * nested, (scope, len(whiles))
        for extent in extents:
            assert any(re.search(rf"\[(\d+,)*{chunk},{extent}\]", line)
                       for line in lines), (scope, extent)


def test_step_fits_a_chip_by_xlas_own_total(step, cell, record_property):
    mem = step[2]
    nbytes = total(mem)
    record_property("keyevl2_b1_s16384_bytes", nbytes)
    print(f"keyevl2-train-1chip step: {nbytes / 1e9:.2f} GB "
          f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f})")
    plan = cell.plan
    # XLA's own total stays a GiB under the chip's 15.75 GiB, and under
    # what the plan reckoned: state, the base set, the reserve with its
    # six bytes a pair, and what is kept beside.
    assert nbytes <= HBM_BYTES - 2 ** 30
    assert nbytes <= plan.state_bytes + plan.base_bytes \
        + plan.reserve_bytes + plan.kept_extra_bytes + 2 ** 25
    # 12,147,829,248 with 2.32 GB kept beside the base set, where 0.81 kept
    # read 11,305,062,400 (PR 61's tree; 15,106,948,608 on PR 60's, before
    # dI was written over the rows of I it was read from).
    assert nbytes <= 12_250_000_000
    # The schedule PR 61 won holds: XLA runs each layer's target and
    # backward kernel before the next layer's scores are made, and does not
    # put four layers' off until after the head's loss. A schedule that
    # fell back would hold their I beside what is kept: the total less what
    # is kept read 10,490,318,464 there and reads 9,823,135,872 here.
    assert nbytes - plan.kept_extra_bytes <= 10_500_000_000
