"""Gated short convolution: LFM2's sequence mixer between its projections.

    gated_short_conv(bcx [b, S, 3d], weight [K, d], tail=None)
        -> (y [b, S, d], the new tail [b, K - 1, d])

`bcx` is B | C | x side by side (one input projection's thirds, in that
order), and

    u[t] = B[t] * x[t]                         rounded to bcx's dtype
    c[t] = sum_j weight[j] * u[t - (K - 1) + j]    per channel, causal
    y[t] = C[t] * c[t]

with zeros before a sequence's first token, or `tail`, the K - 1 rows of u
before it (what a cache keeps), and nothing read across the sequences of a
batch. No activation and no bias. Products and sums in float32, values in
bcx's dtype.

Two forms. The plain one, K shifted products of `ops.layers`, is the CPU
path, a cache's prefill and single-token step (any `tail`), and what the
kernels are checked against. On a TPU backend (interpreted where
RAY_TPU_PALLAS_INTERPRET=1, by the rule of ops/attention.py), from a zero
tail and at a width that is whole 128-lane tiles, two Mosaic kernels under
one `jax.custom_vjp`:

* `_conv_fwd_kernel`, scope `short_conv_fwd`: one pass over row blocks x
  column blocks of the [S, 3d] projection. A block reads its B, C and x
  thirds and the 16 rows of B and x before it (the halo: zeros at a
  sequence's first block, so no row of another sequence is ever read),
  lays u out behind its halo in VMEM, reads the K shifted views of that
  and writes y. Least traffic 4 S d values.
* `_conv_bwd_kernel`, scope `short_conv_bwd`: with g = C * dy,

      dC    = dy * c                               c made again, not kept
      du[t] = sum_j weight[j] * g[t + (K - 1) - j]     rows AHEAD, zeros
                                                   past the sequence's end
      dB    = du * x,   dx = du * B
      dw[j] = sum_t g[t] * u[t - (K - 1) + j]          float32

  A block reads B, C, x, dy, the halo of B and x before it and the halo
  of C and dy after it; it computes all three gradients once and hands
  them to the [S, 3d] result a third a grid step (the inputs' blocks do
  not move between those steps, so nothing is read again). The taps'
  gradient is accumulated in float32 over the row blocks and sequences of
  a column block, eight partial rows a tap, summed outside. Least traffic
  7 S d values.

The residuals are the function's inputs. A sequence length that is not a
whole number of row blocks is padded with zero rows, which a causal
convolution's real rows never read, and cut again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention
from .layers import _taps, _windows

# Rows and columns of a block: the largest of these and their halvings
# that fit (rows: the sequence rounded up to the halo; columns: a divisor
# of the width, 128 lanes at least).
_BLOCK_ROWS = 512
_BLOCK_COLS = 512
# The halo: the rows of u before a block (of g after it) a tap may read,
# one bfloat16 tile's sublanes, so every block a kernel is handed is whole
# tiles. K - 1 may not pass it.
_HALO = 16
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _plain(bcx, weight, tail):
    """The K shifted products of (tail | u), float32 sums; returns (y,
    the last K - 1 rows of (tail | u))."""
    K = weight.shape[0]
    B, C, x = jnp.split(bcx, 3, axis=-1)
    u = B * x
    c = _taps(_windows(u, tail, K), weight.T, None, jnp.float32)
    y = (C.astype(jnp.float32) * c).astype(bcx.dtype)
    return y, jnp.concatenate([tail, u], axis=1)[:, u.shape[1]:]


def _blocks(S: int, d: int):
    """(rows of a block, the padded sequence, columns of a block)."""
    rows = min(_BLOCK_ROWS, -(-S // _HALO) * _HALO)
    cols = next(c for c in (_BLOCK_COLS, 256, 128) if d % c == 0)
    return rows, -(-S // rows) * rows, cols


def _u(b_ref, x_ref, dtype):
    """B * x of two blocks, rounded as a cache holds it, in float32."""
    f32 = jnp.float32
    return (b_ref[...].astype(f32) * x_ref[...].astype(f32)).astype(
        dtype).astype(f32)


def _shifted(buf, first: int, rows: int, w, taps: int, step: int):
    """sum_j w[j] * buf[first + step * j : ... + rows]: the K shifted views
    of a block laid out beside its halo, each times its tap's row."""
    from jax.experimental import pallas as pl

    acc = None
    for j in range(taps):
        term = buf[pl.ds(first + step * j, rows), :] * w[j:j + 1, :]
        acc = term if acc is None else acc + term
    return acc


def _conv_fwd_kernel(b_ref, c_ref, x_ref, bp_ref, xp_ref, w_ref, y_ref,
                     ubuf, *, taps: int, rows: int):
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, y_ref.dtype
    start = pl.program_id(1) == 0           # a sequence's first block
    ubuf[0:_HALO, :] = jnp.where(start, 0.0, _u(bp_ref, xp_ref, dtype))
    ubuf[_HALO:_HALO + rows, :] = _u(b_ref, x_ref, dtype)
    c = _shifted(ubuf, _HALO - (taps - 1), rows, w_ref[...], taps, 1)
    y_ref[...] = (c_ref[...].astype(f32) * c).astype(dtype)


def _conv_bwd_kernel(b_ref, c_ref, x_ref, dy_ref, bp_ref, xp_ref, cn_ref,
                     dyn_ref, w_ref, dbcx_ref, dw_ref, ubuf, gbuf, stash, *,
                     taps: int, rows: int):
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, dbcx_ref.dtype
    seq, block, part = (pl.program_id(i) for i in (1, 2, 3))

    @pl.when(part == 0)
    def _all_three():
        B, X = b_ref[...].astype(f32), x_ref[...].astype(f32)
        dy = dy_ref[...].astype(f32)
        g = c_ref[...].astype(f32) * dy
        ubuf[0:_HALO, :] = jnp.where(block == 0, 0.0,
                                     _u(bp_ref, xp_ref, dtype))
        ubuf[_HALO:_HALO + rows, :] = (B * X).astype(dtype).astype(f32)
        gbuf[0:rows, :] = g
        gbuf[rows:rows + _HALO, :] = jnp.where(
            block == pl.num_programs(2) - 1, 0.0,
            cn_ref[...].astype(f32) * dyn_ref[...].astype(f32))
        w = w_ref[...]
        first = _HALO - (taps - 1)
        c = _shifted(ubuf, first, rows, w, taps, 1)
        du = _shifted(gbuf, taps - 1, rows, w, taps, -1)
        stash[0] = (du * X).astype(dtype)
        stash[1] = (dy * c).astype(dtype)
        stash[2] = (du * B).astype(dtype)
        fresh = (seq == 0) & (block == 0)
        for j in range(taps):
            partial = jnp.sum(
                (g * ubuf[pl.ds(first + j, rows), :]).reshape(
                    rows // 8, 8, -1), axis=0)
            dw_ref[j] = jnp.where(fresh, partial, dw_ref[j] + partial)

    dbcx_ref[...] = stash[part]


def _thirds(d: int, cols: int, rows: int, halo_at=None):
    """BlockSpecs of the B, C and x thirds of a [b, S, 3d] array: a
    [rows, cols] block at (sequence, row block, column block), or with
    `halo_at` the _HALO rows `halo_at(row block)` says, in units of the
    halo. The index maps take the grid's indices as (sequence, row block,
    column block)."""
    from jax.experimental import pallas as pl

    def spec(third):
        if halo_at is None:
            return pl.BlockSpec(
                (None, rows, cols),
                lambda s, r, c: (s, r, c + third * (d // cols)))
        return pl.BlockSpec(
            (None, _HALO, cols),
            lambda s, r, c: (s, halo_at(r), c + third * (d // cols)))

    return [spec(third) for third in range(3)]


def _forward_call(bcx, weight):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, S, _), (K, d) = bcx.shape, weight.shape
    rows, padded, cols = _blocks(S, d)
    if padded != S:
        bcx = jnp.pad(bcx, ((0, 0), (0, padded - S), (0, 0)))
    per = rows // _HALO

    def before(r):
        return jnp.maximum(r * per - 1, 0)

    B, C, x = _thirds(d, cols, rows)
    Bp, _, xp = _thirds(d, cols, rows, before)
    call = pl.pallas_call(
        functools.partial(_conv_fwd_kernel, taps=K, rows=rows),
        out_shape=jax.ShapeDtypeStruct((b, padded, d), bcx.dtype),
        grid=(b, padded // rows, d // cols),
        in_specs=[B, C, x, Bp, xp,
                  pl.BlockSpec((K, cols), lambda s, r, c: (0, c))],
        out_specs=pl.BlockSpec((None, rows, cols), lambda s, r, c: (s, r, c)),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=attention._interpret(),
    )
    # A scope directly round each pallas_call: it reaches the name of the
    # HLO instruction, which is what a device trace shows
    # (util/profiling.py DEVICE_SCOPES).
    with jax.named_scope("short_conv_fwd"):
        y = call(bcx, bcx, bcx, bcx, bcx, weight.astype(jnp.float32))
    return y[:, :S]


def _backward_call(bcx, weight, dy):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, S, _), (K, d) = bcx.shape, weight.shape
    rows, padded, cols = _blocks(S, d)
    if padded != S:
        pad = ((0, 0), (0, padded - S), (0, 0))
        bcx, dy = jnp.pad(bcx, pad), jnp.pad(dy, pad)
    per, last = rows // _HALO, padded // _HALO - 1

    def before(r):
        return jnp.maximum(r * per - 1, 0)

    def after(r):
        return jnp.minimum((r + 1) * per, last)

    # The grid is (column block, sequence, row block, third): a column
    # block's taps' gradient stays in VMEM over its sequences and row
    # blocks, and a block's three gradients go out one a step.
    def grid_order(spec):
        return pl.BlockSpec(spec.block_shape,
                            lambda c, s, r, p: spec.index_map(s, r, c))

    B, C, x = _thirds(d, cols, rows)
    Bp, _, xp = _thirds(d, cols, rows, before)
    _, Cn, _ = _thirds(d, cols, rows, after)
    whole = pl.BlockSpec((None, rows, cols), lambda s, r, c: (s, r, c))
    ahead = pl.BlockSpec((None, _HALO, cols),
                         lambda s, r, c: (s, after(r), c))
    call = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, taps=K, rows=rows),
        out_shape=(jax.ShapeDtypeStruct((b, padded, 3 * d), bcx.dtype),
                   jax.ShapeDtypeStruct((K, 8, d), jnp.float32)),
        grid=(d // cols, b, padded // rows, 3),
        in_specs=[grid_order(s) for s in (B, C, x, whole, Bp, xp, Cn, ahead)]
        + [pl.BlockSpec((K, cols), lambda c, s, r, p: (0, c))],
        out_specs=(
            pl.BlockSpec((None, rows, cols),
                         lambda c, s, r, p: (s, r, c + p * (d // cols))),
            pl.BlockSpec((K, 8, cols), lambda c, s, r, p: (0, 0, c))),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cols), jnp.float32),
                        pltpu.VMEM((rows + _HALO, cols), jnp.float32),
                        pltpu.VMEM((3, rows, cols), bcx.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=attention._interpret(),
    )
    with jax.named_scope("short_conv_bwd"):
        dbcx, dw = call(bcx, bcx, bcx, dy, bcx, bcx, bcx, dy,
                        weight.astype(jnp.float32))
    return dbcx[:, :S], jnp.sum(dw, axis=1).astype(weight.dtype)


@jax.custom_vjp
def _gated_conv(bcx, weight):
    return _forward_call(bcx, weight)


def _gated_conv_fwd(bcx, weight):
    return _forward_call(bcx, weight), (bcx, weight)


@jax.named_scope("short_conv_bwd")
def _gated_conv_bwd(residuals, dy):
    return _backward_call(*residuals, dy)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_short_conv(bcx, weight, tail=None):
    """y = C * conv_K(B * x) of bcx = B | C | x [b, S, 3d] under the taps
    `weight` [K, d] (the last one on the current position), from `tail`
    [b, K - 1, d], the rows of B * x before the first (zeros where None).
    Returns (y [b, S, d], the last K - 1 rows of (tail | B * x): what a
    cache hands to the next call). The module's docstring has the
    equations and which form runs where."""
    K, d = weight.shape
    assert bcx.shape[-1] == 3 * d and K - 1 <= _HALO, (bcx.shape, weight.shape)
    if tail is None and d % 128 == 0 and attention._on_tpu():
        # The new tail is plain slicing beside the rule: nothing at all
        # in a train step, which hands no tail on.
        batch, S = bcx.shape[:2]
        kept = min(S, K - 1)
        B, _, x = jnp.split(bcx[:, S - kept:], 3, axis=-1)
        before = jnp.zeros((batch, K - 1, d), bcx.dtype)
        return (_gated_conv(bcx, weight),
                jnp.concatenate([before, B * x], axis=1)[:, kept:])
    if tail is None:
        tail = jnp.zeros((bcx.shape[0], K - 1, d), bcx.dtype)
    return _plain(bcx, weight, tail.astype(bcx.dtype))
