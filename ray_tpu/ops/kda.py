"""Chunked delta rule with a decay a key channel (Kimi Delta Attention,
arXiv:2510.26692): Pallas TPU kernels + the token-by-token recurrence.

The recurrence, per head (q_t, k_t in R^K already L2-normalised, v_t in
R^V, g_t in (lower_bound, 0)^K the log of the step's decay, ONE NUMBER A
KEY CHANNEL, beta_t in (0, 1)):

    S' = Diag(exp(g_t)) S_{t-1}                 S in R^{K x V}, float32
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

ops/gated_delta.py is this with one decay a head, and its chunked form
stands on (k_i . k_j) D_ij: the pair's decay leaves the sum over channels.
Here it does not. With G_i in R^K the running sum of g inside a chunk
(inclusive) and P_ij[x, z] = sum_c x_ic z_jc exp(G_ic - G_jc):

    A = strict_lower(beta_i P_ij[k, k])          T = (I + A)^-1
    W = T (beta * K * exp(G))                    U = T (beta * V)
    V' = U - W S_prev
    O  = (Q * exp(G)) S_prev + lower(P_ij[q, k]) V'
    S_next = Diag(exp(G_C)) S_prev + (K * exp(G_C - G))^T V'

P is made a product again by a reference row: exp(G_i - G_j) = exp(G_i -
G_r) exp(G_r - G_j), the first factor on x's rows, the second on z's. The
first is <= 1 where r <= i; the second is <= 1 only where j <= r, and
GROWS where r < j <= i. So a chunk is worked in sub-blocks of `SUB` = 16
rows, r the first row of i's sub-block: the second factor is then at most
exp(15 * -lower_bound), 75 at the model's bound of -5 a step, under ln of
float32's largest (88.7). `kda_plan` refuses a bound the sub-block does not
hold (it does not clamp). The four row strips of a chunk of 64 are ONE
product: the strips' rows stand in their own 128-lane block of a [C, 4K]
operand (zeros elsewhere) against [Z_0 | Z_1 | Z_2 | Z_3], Z_I = z *
exp(G_rI - G) on the rows up to strip I's last and 0 below; q's and k's
rows are stacked, so both P tiles of a head and chunk are one [2C, 4K] x
[4K, C] product. Entries above the diagonal inside a diagonal sub-block
come out large and finite, and are masked. Every exponent is masked BEFORE
the exp.

G is made inside the kernels, from g itself: a grid program sums its block
of g [C, heads_per_program * K] float32 down the chunk's rows before its
heads are worked (`_running_sums`: log2(C) rolled, masked additions on the
vector unit), and the backward kernel sums the heads' gradients by G the
other way, from a row to its chunk's end, before the block is written: the
gradient by g. Float32 additions only: G reaches 64 * lower_bound at a
chunk's end, where one rounding to bfloat16 is off by more than 1, and the
gate's bias sums the gradient over every token. HBM holds g and its
gradient and no running sum (as an XLA window sum over [16384, 4096] and
its transpose round the kernels they were 45.7 ms of a 911.7 ms step of
five layers: PERF.md section 6, PR 64).

T is ops/gated_delta.py's `_unit_lower_inverse` (two heads' tiles side by
side at chunks of 64), the states' layout and the T - I a differentiated
forward leaves in HBM are that module's too; the states are left in the
model's dtype, the operand every product of a chunk takes them as (the
carried state stays float32 in VMEM), half of float32's 537 MB a layer at
16,384 tokens of 32 heads of 128 x 128. A grid program works
`heads_per_program` heads of one chunk (the grid: batch, head groups,
chunks; the chunk axis sequential), each head's columns cut out of the
projections' own layout [batch, seq, heads * width]; K and V are whole
128-lane tiles here.

The backward kernel makes the tiles again from the state entering the chunk
and the kept T - I, as gated_delta's does. With a decay a channel the
gradient by G is elementwise, no row and column sums of a tile: where x's
row i met P with gradient dx_ic, dG_ic += x_ic dx_ic, and where k's row j
met it as a column, dG_jc -= k_jc dk_jc, both from the operands as the
products took them, so that what a chunk's sum cancels it cancels to
float32 (`_head_backward` says what it cost when it did not).

`kda_plan` gives the sizes and counts what runs; `kda_reference` is the
recurrence token by token in float32, for the tests, other backends and
lengths that are no whole number of chunks.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention
from .attention import DEFAULT_MASK_VALUE, _NN, _NT, _dot
from .gated_delta import (LANES, VMEM_LIMIT, _TN, _inverse_levels,
                          _unit_lower_inverse, heads_per_tile)

SUB = 16                    # rows of a sub-block: one reference row each
LOWER_BOUND = -5.0          # the model's bound on a step's log-decay
# exp's argument inside a diagonal sub-block reaches (SUB - 1) * -bound;
# float32's largest is exp(88.7), and a P entry sums 128 channels.
_EXP_ROOM = 83.0


# ---------------------------------------------------------------------------
# Reference: the recurrence, token by token
# ---------------------------------------------------------------------------
def kda_reference(q, k, v, g, beta, chunk: int = 64, initial_state=None):
    """The recurrence as written above, one token a step of a `lax.scan`,
    float32; any length (`chunk` is taken for the signature's sake). q, k,
    g [b, L, H, K]; v [b, L, H, V]; beta [b, L, H]. Returns (o in v's dtype,
    the final state [b, H, K, V] float32)."""
    del chunk
    f32 = jnp.float32
    b, L, H, K = q.shape
    S0 = (jnp.zeros((b, H, K, v.shape[-1]), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    by_token = tuple(jnp.moveaxis(t.astype(f32), 1, 0)
                     for t in (q, k, v, g, beta))
    final, o = jax.lax.scan(step, S0, by_token)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), final


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KdaPlan:
    """Sizes of one kda_rule call and what a sequence of one batch row
    executes. A grid program works `heads_per_program` heads of one chunk;
    `sub_blocks` reference rows a chunk. `fwd_matmuls` / `bwd_matmuls`
    count the products on the matrix unit (`inverse_matmuls` the forward's
    float32 ones that make T, two a level and pair of heads),
    `fwd_exps` / `bwd_exps` the exponentials' [chunk, K] tiles: the row
    factor, one column factor a sub-block, exp(G) and exp(G_C - G).
    The running sums of g and their transpose are the vector unit's and
    in neither count.
    `state_bytes` the chunk states in HBM (bfloat16: the operand the
    chunk's products take, where ops/gated_delta.py keeps float32),
    `kept_bytes` T - I a head and chunk (bfloat16), `decay_bytes` the
    float32 g [L, H * K] (its gradient is as much). At
    Ling-3.0-flash's shape, `kda_plan(16384, 32, 128, 128, 64)`:
    `inverse_matmuls` 40,960, `fwd_matmuls` 98,304, `bwd_matmuls` 155,648,
    `fwd_exps` 57,344 = `bwd_exps`."""
    seq_len: int
    chunk: int
    chunks: int
    sub_blocks: int
    heads_per_program: int
    grid: tuple
    lower_bound: float
    vmem_bytes: int
    state_bytes: int
    kept_bytes: int
    decay_bytes: int
    inverse_matmuls: int
    fwd_matmuls: int
    bwd_matmuls: int
    fwd_exps: int
    bwd_exps: int


# What one head of one chunk runs besides T (`_head_forward`,
# `_head_backward`): the P tiles' one product, W, U, V', O's two and S1's;
# the backward makes the first four again and runs fifteen of its own.
_FWD_PRODUCTS = 7
_AGAIN_PRODUCTS = 4
_BWD_PRODUCTS = 15


def heads_per_program(heads: int) -> int:
    """Heads one grid program works: the largest of 8, 4, 2 that divides
    the count (pairs share the inverse's chain; more heads a program give
    the scheduler independent chains to interleave)."""
    for n in (8, 4, 2):
        if heads % n == 0:
            return n
    return 1


def _vmem_bytes(per_program: int, key_dim: int, value_dim: int,
                chunk: int) -> int:
    """What the backward kernel holds: double-buffered blocks (q, k, dq,
    dk, v, dO, dv in bf16, g and dg in float32, T - I; three state blocks),
    a head's float32 temporaries (the [2C, 4K] operands of P) and the
    sums' (`_running_sums`): the block's G, the heads' gradients by G read
    back to be summed, and a step's rolled copy, float32 [C, per_program *
    K] each."""
    subs = chunk // SUB
    state = per_program * key_dim * value_dim * 4
    acts = chunk * per_program * (
        (4 * key_dim + 3 * value_dim + chunk) * 2 + 2 * key_dim * 4)
    head = (6 * chunk * subs * key_dim + 12 * chunk * (key_dim + value_dim)
            + 16 * chunk * chunk) * 4
    sums = 3 * chunk * per_program * key_dim * 4
    return 2 * acts + 2 * 3 * state + head + sums


def kda_plan(seq_len: int, heads: int, key_dim: int, value_dim: int,
             chunk: int, lower_bound: float = LOWER_BOUND) -> KdaPlan:
    """The tiling `kda_rule` runs a [.., seq_len, heads, .] call at, and
    the one place the gate's bound is held to the sub-block: a
    `lower_bound` whose (SUB - 1) steps pass what float32's exp holds is
    refused (at SUB = 16: under -5.5)."""
    if seq_len % chunk:
        raise ValueError(f"the kernels work whole chunks of {chunk}, not "
                         f"{seq_len} positions")
    if chunk % SUB:
        raise ValueError(f"kda: chunks of {chunk} are no whole number of "
                         f"sub-blocks of {SUB}")
    if not -_EXP_ROOM / (SUB - 1) <= lower_bound <= 0:
        raise ValueError(
            f"kda: a log-decay down to {lower_bound} a step reaches "
            f"{-(SUB - 1) * lower_bound:.0f} inside a sub-block of {SUB} "
            f"rows, past what float32's exp holds ({_EXP_ROOM:.0f}); the "
            f"gate's bound is the model's to keep, it is not clamped here")
    if key_dim % LANES or value_dim % LANES:
        raise ValueError(f"kda: heads of {key_dim} x {value_dim} are no "
                         f"whole {LANES}-lane tiles")
    per_program = heads_per_program(heads)
    need = _vmem_bytes(per_program, key_dim, value_dim, chunk)
    if need > VMEM_LIMIT:
        raise ValueError(
            f"kda: chunks of {chunk} with {per_program} heads of "
            f"{key_dim} x {value_dim} do not fit {VMEM_LIMIT} bytes of VMEM")
    chunks, subs = seq_len // chunk, chunk // SUB
    pairs = chunks * -(-heads // heads_per_tile(chunk))
    inverse = pairs * 2 * _inverse_levels(chunk)
    exps = chunks * heads * (subs + 3)
    return KdaPlan(
        seq_len=seq_len, chunk=chunk, chunks=chunks, sub_blocks=subs,
        heads_per_program=per_program,
        grid=(heads // per_program, chunks), lower_bound=lower_bound,
        vmem_bytes=need,
        state_bytes=chunks * heads * key_dim * value_dim * 2,
        kept_bytes=chunks * heads * chunk * chunk * 2,
        decay_bytes=seq_len * heads * key_dim * 4,
        inverse_matmuls=inverse,
        fwd_matmuls=inverse + chunks * heads * _FWD_PRODUCTS,
        bwd_matmuls=chunks * heads * (_AGAIN_PRODUCTS + _BWD_PRODUCTS),
        fwd_exps=exps, bwd_exps=exps)


def _kernel_ok(q, v, chunk: int, H: int) -> bool:
    """Whether the kernels run this call (q [b, L, H * K], v [b, L, H *
    V]): on a TPU (or interpreted), whole chunks of whole sub-blocks, heads
    of whole 128-lane tiles."""
    if not attention._on_tpu() or q.shape[1] % chunk or chunk % SUB:
        return False
    return q.shape[-1] % (H * LANES) == 0 and v.shape[-1] % (H * LANES) == 0


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
def _to_col(row):
    """A [1, n] row as the [n, 1] column of the same values, to the bit:
    the row against the identity's mask, summed along the lanes."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _to_row(col):
    """A [n, 1] column as the [1, n] row of the same values."""
    n = col.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(col, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _in_blocks(x, block, subs: int):
    """x [C, K] -> [C, subs * K]: sub-block I's rows in lane block I,
    zeros elsewhere (`block` [C, 1]: a row's sub-block)."""
    return jnp.concatenate(
        [jnp.where(block == I, x, jnp.zeros_like(x)) for I in range(subs)],
        axis=1)


def _own_block(wide, block, subs: int):
    """The reverse reading: wide [C, subs * K] -> [C, K], a row taking the
    lane block of its own sub-block."""
    K = wide.shape[1] // subs
    out = jnp.where(block == 0, wide[:, :K], 0.0)
    for I in range(1, subs):
        out = jnp.where(block == I, wide[:, I * K:(I + 1) * K], out)
    return out


def _running_sums(x, to_end: bool = False):
    """x [C, n] float32 summed along the rows inside the chunk, float32:
    row i the sum of rows 0..i, or of rows i..C-1 where `to_end` (the
    transpose: what a gradient by the running sums is by their terms).
    log2(C) steps on the vector unit, each the block rolled along its
    sublanes by twice the step before, masked where it wrapped, and added:
    float32 additions and nothing else. x or its sums rounded ONCE to
    bfloat16 would be off by more than 1 at a chunk's end (G reaches 64 *
    -5 there) and, in the gradient, is PR 63's finding (a) over again. The
    kernel pair alone on the chip, a layer at 16,384 tokens, forward +
    backward: this 22.96 ms; as products with the triangle of ones, x in
    three bfloat16 pieces that sum to it, 23.80 to 24.34, as one float32
    product at `Precision.HIGHEST` 24.97; the window sum of the whole
    [16384, 4096] array and its transpose in XLA round the kernels 27.59
    (my chip runs, PR 64; PERF.md section 6 has the forward's and why the
    same product on the whole array in XLA was worse still)."""
    from jax.experimental.pallas import tpu as pltpu

    chunk = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    step = 1
    while step < chunk:
        if to_end:      # row i takes row i + step
            x = x + jnp.where(row < chunk - step,
                              pltpu.roll(x, chunk - step, 0), 0.0)
        else:           # row i takes row i - step
            x = x + jnp.where(row >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _head_tiles(q, k, G, chunk: int):
    """What both passes make of one head's decays in one chunk. q, k
    [C, K] in the model's dtype, G [C, K] float32 the running sums.
    Returns a dict: `R` [C, K] the row factor exp(G - G_r), r the first row
    of the row's sub-block; `Es` the column factors, one [C, K] a
    sub-block I, exp(G_rI - G) on the rows up to I's last and 0 below;
    `Zcat` [C, subs * K] = [k E_0 | k E_1 | ...] and `Xcat` [2C, subs * K]
    (q's rows, then k's, times R, each sub-block's in its own lane block)
    in the model's dtype; `Pqk`, `Pkk` [C, C] float32, right on and under
    the diagonal and to be masked above it."""
    dtype, f32 = q.dtype, jnp.float32
    subs = chunk // SUB
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    block = row >> (SUB.bit_length() - 1)
    refs = [G[I * SUB:I * SUB + 1, :] for I in range(subs)]     # [1, K]
    G_ref = refs[-1]
    for I in reversed(range(subs - 1)):
        G_ref = jnp.where(block <= I, refs[I], G_ref)
    R = jnp.exp(G - G_ref)
    Es = [jnp.exp(jnp.where(row < (I + 1) * SUB, refs[I] - G,
                            DEFAULT_MASK_VALUE)) for I in range(subs)]
    k32 = k.astype(f32)
    Zcat = jnp.concatenate([(k32 * E).astype(dtype) for E in Es], axis=1)
    Xcat = jnp.concatenate(
        [_in_blocks((q.astype(f32) * R).astype(dtype), block, subs),
         _in_blocks((k32 * R).astype(dtype), block, subs)], axis=0)
    PP = _dot(Xcat, Zcat, _NT)                                  # [2C, C]
    return dict(R=R, Es=Es, Zcat=Zcat, Xcat=Xcat, block=block,
                Pqk=PP[:chunk], Pkk=PP[chunk:])


def _head_chunk(q, k, v, G, bc, S0, Tm, Pqk):
    """One head of one chunk up to V', from the state entering and T: what
    the forward makes once and the backward again. q, k [C, K], v [C, V]
    and Tm = T - I [C, C] in the model's dtype; G [C, K] float32, bc [C, 1]
    beta; Pqk [C, C] float32 unmasked; S0 [K, V] float32."""
    dtype, f32 = q.dtype, jnp.float32
    chunk = q.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    k32, v32, q32 = k.astype(f32), v.astype(f32), q.astype(f32)
    eg = jnp.exp(G)
    end = G[chunk - 1:chunk, :]                                 # [1, K]
    to_end, exp_end = jnp.exp(end - G), jnp.exp(end)
    Kbg, Vb = k32 * eg * bc, v32 * bc
    W = Kbg + _dot(Tm, Kbg.astype(dtype), _NN)
    U = Vb + _dot(Tm, Vb.astype(dtype), _NN)
    S0b = S0.astype(dtype)
    Vp = U - _dot(W.astype(dtype), S0b, _NN)
    return dict(rows=rows, cols=cols, k32=k32, v32=v32, q32=q32, eg=eg,
                to_end=to_end, exp_end=exp_end, Kbg=Kbg, W=W, U=U, S0b=S0b,
                Pl=jnp.where(rows >= cols, Pqk, 0.0).astype(dtype),
                Qg=(q32 * eg).astype(dtype), Vpb=Vp.astype(dtype),
                Kd=(k32 * to_end).astype(dtype))


def _tile_forward(heads, chunk: int):
    """The heads of one 128-lane tile of [C, C] tiles (two at chunks of
    64) of one chunk; `heads`: a head's (q, k, v, G, bc, S0). Up to T the
    heads share one chain of the inverse's products. Returns (Tm = T - I
    in the model's dtype [C, n * C], a head's (O [C, V] float32, S1))."""
    tiles = [_head_tiles(q, k, G, chunk) for q, k, _, G, _, _ in heads]
    n = len(heads)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, n * chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, n * chunk), 1)
    cols = lane if n == 1 else lane % chunk
    bcs = heads[-1][4]
    for i in reversed(range(n - 1)):
        bcs = jnp.where(lane < (i + 1) * chunk, heads[i][4], bcs)
    A = jnp.where(
        rows > cols,
        bcs * jnp.concatenate([t["Pkk"] for t in tiles], axis=1), 0.0)
    Tm = _unit_lower_inverse(A, chunk).astype(heads[0][0].dtype)
    outs = []
    for i, ((q, k, v, G, bc, S0), tile) in enumerate(zip(heads, tiles,
                                                         strict=True)):
        own = slice(i * chunk, (i + 1) * chunk)
        t = _head_chunk(q, k, v, G, bc, S0, Tm[:, own], tile["Pqk"])
        O = _dot(t["Qg"], t["S0b"], _NN) + _dot(t["Pl"], t["Vpb"], _NN)
        S1 = _to_col(t["exp_end"]) * S0 + _dot(t["Kd"], t["Vpb"], _TN)
        outs.append((O, S1))
    return Tm, outs


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, init_ref, o_ref,
                    states_ref, final_ref, T_ref=None, *, hb: int, K: int,
                    V: int):
    """One chunk of `hb` heads. `g_ref` the log-decays [C, hb * K]
    float32, whose running sums G are made here for the whole block;
    `b_ref` beta [C, hb]; `T_ref` the heads' T - I [C, hb * C] where a
    backward pass will read it."""
    from jax.experimental import pallas as pl

    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        final_ref[...] = init_ref[...]

    b_all = b_ref[0, 0]
    G_all = _running_sums(g_ref[0])
    n = heads_per_tile(chunk)
    for first in range(0, hb, n):
        tile = range(first, min(first + n, hb))
        lanes = slice(first * chunk, tile.stop * chunk)
        heads = []
        for h in tile:
            ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
            S0 = final_ref[0, h]
            states_ref[0, 0, h] = S0.astype(states_ref.dtype)
            heads.append((q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
                          G_all[:, ks], b_all[:, h:h + 1], S0))
        Tm, outs = _tile_forward(heads, chunk)
        if T_ref is not None:
            T_ref[0, 0, :, lanes] = Tm
        for h, (O, S1) in zip(tile, outs, strict=True):
            o_ref[0, :, h * V:(h + 1) * V] = O.astype(o_ref.dtype)
            final_ref[0, h] = S1


def _head_backward(q, k, v, G, bc, S0, Tm, dO, dS1, chunk: int):
    """Every gradient of one head's work in one chunk: (dq, dk, dG [C, K],
    dv [C, V], dbeta [C, 1], dS0 [K, V]), float32. The tiles, W, U and V'
    are made again from S0 and the Tm = T - I the forward kept."""
    dtype, f32 = q.dtype, jnp.float32
    subs = chunk // SUB
    K = q.shape[1]
    tile = _head_tiles(q, k, G, chunk)
    t = _head_chunk(q, k, v, G, bc, S0, Tm, tile["Pqk"])
    rows, cols = t["rows"], t["cols"]
    eg, to_end, exp_end = t["eg"], t["to_end"], t["exp_end"]
    S0b, Vpb, k32, q32 = t["S0b"], t["Vpb"], t["k32"], t["q32"]
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dS1b = dS1.astype(dtype)
    # through O and the state handed on
    dVp = _dot(t["Pl"], dO, _TN) + _dot(t["Kd"], dS1b, _NN)
    dPl = jnp.where(rows >= cols, _dot(dO, Vpb, _NT), 0.0)
    dQg = _dot(dO, S0b, _NT)
    dKd = _dot(Vpb, dS1b, _NT)
    dVpb = dVp.astype(dtype)
    dS0 = (_dot(t["Qg"], dO, _TN) + _to_col(exp_end) * dS1
           - _dot(t["W"].astype(dtype), dVpb, _TN))
    # through W, U and T: T^T applied as I + (T - I)^T
    dW = -_dot(dVpb, S0b, _NT)
    dKbg = dW + _dot(Tm, dW.astype(dtype), _TN)
    dVb = dVp + _dot(Tm, dVpb, _TN)
    dA = -jnp.where(rows > cols,
                    _dot(dKbg.astype(dtype), t["W"].astype(dtype), _NT)
                    + _dot(dVb.astype(dtype), t["U"].astype(dtype), _NT),
                    0.0)
    dbeta = (rowsum(dA * tile["Pkk"]) + rowsum(dKbg * k32 * eg)
             + rowsum(dVb * t["v32"]))
    # through the two P tiles, q's rows over k's
    dP = jnp.concatenate([dPl, dA * bc], axis=0).astype(dtype)  # [2C, C]
    R = tile["R"]
    dX = _dot(dP, tile["Zcat"], _NN)                            # [2C, 4K]
    dq_P = _own_block(dX[:chunk], tile["block"], subs) * R
    dk_row = _own_block(dX[chunk:], tile["block"], subs) * R
    dZ = _dot(dP, tile["Xcat"], _TN)                            # [C, 4K]
    dk_col = tile["Es"][0] * dZ[:, :K]
    for I in range(1, subs):
        dk_col += tile["Es"][I] * dZ[:, I * K:(I + 1) * K]
    dq = eg * dQg + dq_P
    through_k = bc * eg * dKbg
    held = dKd * to_end
    dk = dk_row + dk_col + through_k + held
    # The tiles' gradient by G, term by term the SAME products on both
    # sides: a pair's term dP_ij x_ic z_jc (x, z the operands as the
    # products took them, rounded) is added at row i and taken off at row
    # j, and a chunk's sum of them is zero (P knows differences of G
    # only). With q * dq on one side and k * dk on the other the two would
    # round different factors and what should cancel would not: summed over
    # the tokens, as the gate's bias sums them, the leftover read a quarter
    # of the gradient on the chip.
    by_row = tile["Xcat"].astype(f32) * dX                      # [2C, 4K]
    by_col = tile["Zcat"].astype(f32) * dZ                      # [C, 4K]
    dG = by_row[:chunk, :K] + by_row[chunk:, :K] - by_col[:, :K]
    for I in range(1, subs):
        own = slice(I * K, (I + 1) * K)
        dG += by_row[:chunk, own] + by_row[chunk:, own] - by_col[:, own]
    dG += k32 * (through_k - held) + eg * dQg * q32
    at_end = (jnp.sum(held * k32, axis=0, keepdims=True)
              + exp_end * _to_row(rowsum(dS1 * S0.astype(f32))))
    dG += jnp.where(last_row, at_end, 0.0)
    return dq, dk, dG, bc * dVb, dbeta, dS0


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, T_ref,
                    do_ref, dfinal_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                    dbeta_ref, dinit_ref, *, hb: int, K: int, V: int):
    """One chunk of `hb` heads, last chunk first. `g_ref` the log-decays
    as the forward kernel took them and G made from them the same way;
    `dg_ref`'s block first collects the heads' gradients by G, then holds
    their sums from each row to the chunk's end: the gradient by g."""
    from jax.experimental import pallas as pl

    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)         # chunks run last to first
    def _init():
        dinit_ref[...] = dfinal_ref[...]

    b_all = b_ref[0, 0]
    G_all = _running_sums(g_ref[0])
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    dbeta_tile = jnp.zeros((chunk, hb), jnp.float32)
    for h in range(hb):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        dq, dk, dG, dv, dbeta, dS0 = _head_backward(
            q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
            G_all[:, ks], b_all[:, h:h + 1], states_ref[0, 0, h],
            T_ref[0, 0, :, h * chunk:(h + 1) * chunk], do_ref[0, :, vs],
            dinit_ref[0, h], chunk)
        dq_ref[0, :, ks] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, ks] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, vs] = dv.astype(dv_ref.dtype)
        dg_ref[0, :, ks] = dG
        dinit_ref[0, h] = dS0
        dbeta_tile = jnp.where(head_lane == h, dbeta, dbeta_tile)
    dbeta_ref[0, 0] = dbeta_tile
    dg_ref[0] = _running_sums(dg_ref[0], to_end=True)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _specs(hb: int, K: int, V: int, chunk: int, chunk_of):
    """BlockSpecs by role, on a grid (batch, head group, chunk step);
    `chunk_of(step)` is the chunk a step works."""
    from jax.experimental import pallas as pl

    return dict(
        key=pl.BlockSpec((1, chunk, hb * K),
                         lambda i, h, s: (i, chunk_of(s), h)),
        value=pl.BlockSpec((1, chunk, hb * V),
                           lambda i, h, s: (i, chunk_of(s), h)),
        # beta by head group, [b, groups, L, hb]: a block's last axis is
        # the whole of the array's
        col=pl.BlockSpec((1, 1, chunk, hb),
                         lambda i, h, s: (i, h, chunk_of(s), 0)),
        state=pl.BlockSpec((1, hb, K, V), lambda i, h, s: (i, h, 0, 0)),
        states=pl.BlockSpec((1, 1, hb, K, V),
                            lambda i, h, s: (i, chunk_of(s), h, 0, 0)),
        inverse=pl.BlockSpec((1, 1, chunk, hb * chunk),
                             lambda i, h, s: (i, chunk_of(s), 0, h)))


def _by_group(x, hb: int):
    """[b, L, H] -> [b, H // hb, L, hb]."""
    b, L, H = x.shape
    return x.reshape(b, L, H // hb, hb).transpose(0, 2, 1, 3)


def _from_groups(x):
    """[b, H // hb, L, hb] -> [b, L, H]."""
    b, n, L, hb = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, L, n * hb)


@functools.partial(jax.jit, static_argnames=("chunk", "H", "keep_inverse"))
def _forward_call(q, k, v, g, beta, init, *, chunk: int, H: int,
                  keep_inverse: bool):
    """q, k [b, L, H*K]; v [b, L, H*V]; g [b, L, H*K] f32; beta [b, L,
    H] f32; init [b, H, K, V] f32 -> (o like v, states [b, chunks, H, K,
    V] in q's dtype: the state ENTERING each chunk as the chunk's products
    take it (the carried state itself stays float32 in VMEM), the final
    state float32, and with `keep_inverse` every head's T - I [b, chunks,
    chunk, H * chunk] in q's dtype)."""
    from jax.experimental import pallas as pl

    b, L, HK = q.shape
    K, V, nc = HK // H, v.shape[-1] // H, L // chunk
    hb = heads_per_program(H)
    s = _specs(hb, K, V, chunk, lambda step: step)
    out_specs = [s["value"], s["states"], s["state"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                 jax.ShapeDtypeStruct((b, nc, H, K, V), q.dtype),
                 jax.ShapeDtypeStruct((b, H, K, V), jnp.float32)]
    if keep_inverse:
        out_specs.append(s["inverse"])
        out_shape.append(
            jax.ShapeDtypeStruct((b, nc, chunk, H * chunk), q.dtype))
    call = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, hb=hb, K=K, V=V),
        grid=(b, H // hb, nc),
        in_specs=[s["key"], s["key"], s["value"], s["key"], s["col"],
                  s["state"]],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("kda_fwd"):
        return call(q, k, v, g, _by_group(beta, hb), init)


@functools.partial(jax.jit, static_argnames=("chunk", "H"))
def _backward_call(q, k, v, g, beta, states, inverse, do, dfinal, *,
                   chunk: int, H: int):
    """`states` and `inverse` as `_forward_call` left them -> (dq, dk, dv,
    dg [b, L, H*K] f32, dbeta [b, L, H] f32, d init)."""
    from jax.experimental import pallas as pl

    b, L, HK = q.shape
    K, V, nc = HK // H, v.shape[-1] // H, L // chunk
    hb = heads_per_program(H)
    s = _specs(hb, K, V, chunk, lambda step: nc - 1 - step)
    call = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, hb=hb, K=K, V=V),
        grid=(b, H // hb, nc),
        in_specs=[s["key"], s["key"], s["value"], s["key"], s["col"],
                  s["states"], s["inverse"], s["value"], s["state"]],
        out_specs=[s["key"], s["key"], s["value"], s["key"], s["col"],
                   s["state"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, H // hb, L, hb), jnp.float32),
                   jax.ShapeDtypeStruct((b, H, K, V), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("kda_bwd"):
        dq, dk, dv, dg, dbeta, dinit = call(
            q, k, v, g, _by_group(beta, hb), states, inverse, do, dfinal)
    return dq, dk, dv, dg, _from_groups(dbeta), dinit


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def _heads_apart(t, H: int):
    """[b, L, H * W] -> [b, L, H, W]."""
    return t.reshape(*t.shape[:2], H, -1)


def kda_rule(q, k, v, g, beta, chunk: int = 64, initial_state=None,
             lower_bound: float = LOWER_BOUND):
    """The delta rule with a decay a key channel over a whole sequence.

    q, k [b, L, H, K] L2-normalised by the caller; v [b, L, H, V]; g [b, L,
    H, K] in (`lower_bound`, 0) the log of each step's decay a channel (the
    caller's gate keeps the bound; `kda_plan` refuses one the sub-blocks do
    not hold); beta [b, L, H]; `initial_state` [b, H, K, V] or None for
    zeros. Returns (o [b, L, H, V] in v's dtype, the final state [b, H, K,
    V] float32). Differentiable in everything but `chunk` and the bound:
    the kernels on a TPU, `kda_reference` elsewhere.

    Inside, the rule and what it keeps for its backward pass are in the
    projections' own layout [b, L, H * width]: the caller's reshapes to
    heads and these back meet and cancel, where a [16384, 32, 128] float32
    residual of a [16384, 4096] value is another tiling on the chip and a
    copy each way (12.2 ms a step of copies no scope owned, my chip run,
    PR 63)."""
    b, L, H, K = q.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, H, K, v.shape[-1]), jnp.float32)

    def flat(t, dtype):
        return t.reshape(b, L, -1).astype(dtype)

    o, final = _rule(flat(q, q.dtype), flat(k, q.dtype), flat(v, q.dtype),
                     flat(g, jnp.float32), beta.astype(jnp.float32),
                     initial_state.astype(jnp.float32), chunk,
                     float(lower_bound), H)
    return _heads_apart(o, H), final


def _by_reference(q, k, v, g, beta, init, chunk, H):
    """`kda_reference` on the rule's flat operands -> (o flat, final)."""
    o, final = kda_reference(*(_heads_apart(t, H) for t in (q, k, v, g)),
                             beta, chunk, init)
    return o.reshape(v.shape), final


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _rule(q, k, v, g, beta, init, chunk, lower_bound, H):
    """q, k, g [b, L, H * K], v [b, L, H * V] -> (o like v, the final
    state). The call that is not differentiated (prefill): no backward
    pass will read T, so the kernel writes none."""
    if not _kernel_ok(q, v, chunk, H):
        return _by_reference(q, k, v, g, beta, init, chunk, H)
    o, _, final = _run_forward(q, k, v, g, beta, init, chunk, lower_bound, H,
                               keep_inverse=False)
    return o, final


def _run_forward(q, k, v, g, beta, init, chunk, lower_bound, H,
                 keep_inverse: bool):
    L = q.shape[1]
    # refuses what does not fit
    kda_plan(L, H, q.shape[-1] // H, v.shape[-1] // H, chunk, lower_bound)
    return _forward_call(q, k, v, g, beta, init,
                         chunk=chunk, H=H, keep_inverse=keep_inverse)


@jax.named_scope("kda_fwd")
def _rule_fwd(q, k, v, g, beta, init, chunk, lower_bound, H):
    if not _kernel_ok(q, v, chunk, H):
        out = _by_reference(q, k, v, g, beta, init, chunk, H)
        return out, (q, k, v, g, beta, init, None, None)
    o, states, final, inverse = _run_forward(
        q, k, v, g, beta, init, chunk, lower_bound, H, keep_inverse=True)
    # What the forward kernel made and a backward pass reads, by name
    # (models/decoder.py KEPT_BY_KIND).
    o = checkpoint_name(o, "kda_o")
    states = checkpoint_name(states, "kda_states")
    inverse = checkpoint_name(inverse, "kda_T")
    return (o, final), (q, k, v, g, beta, init, states, inverse)


@jax.named_scope("kda_bwd")
def _rule_bwd(chunk, lower_bound, H, residuals, cotangents):
    del lower_bound
    q, k, v, g, beta, init, states, inverse = residuals
    do, dfinal = cotangents
    if states is None:
        _, vjp = jax.vjp(
            lambda *args: _by_reference(*args, chunk, H),
            q, k, v, g, beta, init)
        return vjp((do, dfinal))
    return _backward_call(
        q, k, v, g, beta, states, inverse, do.astype(v.dtype),
        dfinal.astype(jnp.float32), chunk=chunk, H=H)


_rule.defvjp(_rule_fwd, _rule_bwd)

assert math.exp(-(SUB - 1) * LOWER_BOUND) < float(jnp.finfo(jnp.float32).max)
