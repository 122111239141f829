"""Grouped (ragged) matmul: rows sorted by group, one matrix per group.

    grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]

Rows `offset[g] : offset[g] + group_sizes[g]` of `lhs` are multiplied by
`rhs[g]`; the sizes are data (a router's counts), the shapes are not. This
is what a dropless mixture-of-experts layer needs (parallel/moe.py): 64
experts of uneven load, no capacity and no padding to it.

Pallas TPU kernels, forward and backward, by the rule of ops/attention.py:
compiled on a TPU backend, interpreted where RAY_TPU_PALLAS_INTERPRET=1,
and `jax.lax.ragged_dot` elsewhere (CPU tests; it is also what the tests
compare the kernels with). Two kernels:

* `_gmm_kernel`: the row tiles of `lhs` are walked in order; a tile that
  straddles a group boundary is visited once per group it touches, and
  each visit stores only its own group's rows. The work list (which
  group, which row tile) is computed by XLA from `group_sizes` and handed
  to the kernel as scalar-prefetch operands, so the index maps can pick
  the group's matrix; the grid's length is the list's, a traced value.
  With `transpose_rhs` it multiplies by `rhs[g].T`: the gradient by `lhs`.
* `_tgmm_kernel`: the gradient by `rhs`, `lhs[rows of g].T @ dout[rows of
  g]` for every g, accumulated over a group's row tiles in float32 and
  stored when the group changes. An empty group gets one visit with an
  empty mask, so its gradient is written as zeros.

bf16 operands at the MXU's rate, float32 accumulation. `group_sizes` may
sum to less than M (an expert layer that holds a share of the experts,
parallel/moe.py: the rows of the absent ones sorted last): no work item
covers a row tile past the sum, so those rows cost no matmul. They belong
to no group: the gradient by `rhs` counts nothing of them (it multiplies
them by zero, so what a caller hands in there must be finite), and the
kernels do not write them (the jax form does, zeros): whatever a caller
reads of an output's or a gradient's rows goes through
`past_groups_zeroed` first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention

# Preferred tile sizes (rows, contraction, columns); each is halved until
# it divides its dimension. The best of eight choices on the v5e at
# OLMoE's shapes, 131,072 rows x 2048 x 1024 over 64 uneven groups: 3.7-4.1
# ms a call against 2.79 ms at the MXU's peak (PERF.md, PR 25); 1024-row
# tiles cost 15% more (more rows of a straddling tile are masked away).
_TILES = (512, 2048, 1024)
# These tiles double-buffered take 22 MiB of VMEM; Mosaic's default scoped
# limit on the v5e is 16 MiB of the chip's 128.
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _pick(dim: int, preferred: int) -> int:
    """The largest of preferred, preferred/2, ..., 128 that divides
    `dim`, else the whole of it (a block as large as its array is always
    a legal block)."""
    b = preferred
    while b >= 128:
        if dim % b == 0:
            return b
        b //= 2
    return dim


def _work_list(group_sizes, m: int, tm: int, visit_empty: bool):
    """(offsets [G+1], item_group [W], item_tile [W], items): work item i
    multiplies row tile item_tile[i] by group item_group[i]; only the
    first `items` (traced) are real. Items are sorted by group and so by
    tile: a tile's visits are consecutive, which is what lets its output
    block stay in VMEM between them."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                      1 if visit_empty else 0)
    item0 = jnp.cumsum(tiles) - tiles
    # Every tile is owned by the group of its first row, and each other
    # group that starts inside it visits it once more.
    length = m // tm + g - 1
    item_group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                            total_repeat_length=length)
    item_tile = (first[item_group] + jnp.arange(length, dtype=jnp.int32)
                 - item0[item_group])
    item_tile = jnp.clip(item_tile, 0, m // tm - 1)
    return offsets, item_group, item_tile, jnp.sum(tiles)


def _row_mask(offsets_ref, group, tile, tm: int, cols: int):
    """[tm, cols] bool: the rows of row tile `tile` that belong to
    `group`."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, cols), 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


def _gmm_kernel(offsets_ref, item_group_ref, item_tile_ref, lhs_ref,
                rhs_ref, out_ref, acc_ref, *, tm: int, tn: int,
                transpose_rhs: bool):
    from jax.experimental import pallas as pl

    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], contract,
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        mine = _row_mask(offsets_ref, item_group_ref[i], item_tile_ref[i],
                         tm, tn)
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool):
    """lhs [M, K] x rhs [G, K, N] -> [M, N], or with `transpose_rhs`
    lhs [M, N] x rhs [G, K, N] -> [M, K]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = _pick(m, _TILES[0]), _pick(k, _TILES[1]), _pick(n, _TILES[2])
    offsets, item_group, item_tile, items = _work_list(
        group_sizes, m, tm, visit_empty=False)

    def lhs_index(n_i, i, k_i, offsets, item_group, item_tile):
        return item_tile[i], k_i

    def rhs_index(n_i, i, k_i, offsets, item_group, item_tile):
        return (item_group[i], n_i, k_i) if transpose_rhs \
            else (item_group[i], k_i, n_i)

    def out_index(n_i, i, k_i, offsets, item_group, item_tile):
        return item_tile[i], n_i

    call = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, items, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=attention._interpret(),
    )
    # A scope directly round each pallas_call: it reaches the name of the
    # HLO instruction, which is what a device trace shows
    # (util/profiling.py DEVICE_SCOPES).
    if transpose_rhs:
        with jax.named_scope("grouped_matmul_dlhs"):
            return call(offsets, item_group, item_tile, lhs, rhs)
    with jax.named_scope("grouped_matmul_fwd"):
        return call(offsets, item_group, item_tile, lhs, rhs)


def _tgmm_kernel(offsets_ref, item_group_ref, item_tile_ref, lhs_ref,
                 dout_ref, out_ref, acc_ref, *, tm: int, tk: int):
    from jax.experimental import pallas as pl

    i, last = pl.program_id(2), pl.num_programs(2) - 1
    group = item_group_ref[i]
    before = item_group_ref[jnp.maximum(i - 1, 0)]
    after = item_group_ref[jnp.minimum(i + 1, last)]

    @pl.when((i == 0) | (before != group))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Rows of the tile that belong to other groups are zeroed in one
    # operand (in float32: the v5e's vector unit has no bf16 select).
    mine = _row_mask(offsets_ref, group, item_tile_ref[i], tm, tk)
    lhs = jnp.where(mine, lhs_ref[...].astype(jnp.float32), 0.0)
    acc_ref[...] += jax.lax.dot_general(
        lhs.T.astype(lhs_ref.dtype), dout_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((i == last) | (after != group))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, dout, group_sizes, out_dtype):
    """lhs [M, K], dout [M, N] -> [G, K, N]: out[g] = lhs[rows of g].T @
    dout[rows of g]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    g = group_sizes.shape[0]
    tm, tk, tn = _pick(m, _TILES[0]), _pick(k, _TILES[1]), _pick(n, _TILES[2])
    offsets, item_group, item_tile, items = _work_list(
        group_sizes, m, tm, visit_empty=True)

    def lhs_index(n_i, k_i, i, offsets, item_group, item_tile):
        return item_tile[i], k_i

    def dout_index(n_i, k_i, i, offsets, item_group, item_tile):
        return item_tile[i], n_i

    def out_index(n_i, k_i, i, offsets, item_group, item_tile):
        return item_group[i], k_i, n_i

    call = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), dout_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, items),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=attention._interpret(),
    )
    with jax.named_scope("grouped_matmul_drhs"):
        return call(offsets, item_group, item_tile, lhs, dout)


def past_groups_zeroed(rows, group_sizes):
    """`rows` [M, .] with every row past the last group zero: what a
    grouped matmul whose sizes sum to less than M leaves there is not
    written. Elementwise, for XLA to fuse into whatever reads the rows
    next."""
    inside = jnp.arange(rows.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(inside[:, None], rows, jnp.zeros((), rows.dtype))


def _ragged_dot(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32
    ).astype(lhs.dtype)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """out[r] = lhs[r] @ rhs[group of row r]: lhs [M, K] with its rows
    sorted by group, rhs [G, K, N], group_sizes [G] int32 summing to M or
    less (the module's docstring has what the rows past the sum hold).
    Output in lhs's dtype, accumulated in float32. Differentiable by lhs
    and rhs."""
    return _forward(lhs, rhs, group_sizes)


def _forward(lhs, rhs, group_sizes):
    if attention._on_tpu():
        return _gmm(lhs, rhs, group_sizes, transpose_rhs=False)
    return _ragged_dot(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return _forward(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


@jax.named_scope("grouped_matmul_bwd")
def grouped_matmul_grads(lhs, rhs, group_sizes, g):
    """(dlhs, drhs) of `grouped_matmul(lhs, rhs, group_sizes)` for the
    output's cotangent `g`: its own gradient rule, and what a rule that
    holds several grouped matmuls (parallel/moe.py) calls for each."""
    if attention._on_tpu():
        dlhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
        drhs = _tgmm(lhs, g, group_sizes, rhs.dtype)
    else:
        _, vjp = jax.vjp(
            lambda a, b: _ragged_dot(a, b, group_sizes), lhs, rhs)
        dlhs, drhs = vjp(g)
    return dlhs, drhs


def _bwd(residuals, g):
    lhs, rhs, group_sizes = residuals
    return (*grouped_matmul_grads(lhs, rhs, group_sizes, g), None)


grouped_matmul.defvjp(_fwd, _bwd)
