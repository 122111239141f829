"""Chunked gated delta rule (Gated DeltaNet, arXiv:2412.06464): Pallas TPU
kernels + jax reference.

The recurrence, per head (q_t, k_t in R^K already L2-normalised, v_t in
R^V, g_t <= 0 the log of the decay, beta_t in (0, 2)):

    S' = exp(g_t) S_{t-1}                       S in R^{K x V}, float32
    u_t = beta_t (v_t - S'^T k_t)               what the state has is read
    S_t = S' + k_t u_t^T                        back before it is written
    o_t = S_t^T q_t

Unlike ops/ssm_scan.py's and ops/selective_scan.py's states, which decay
and accumulate, this one is corrected by what it already holds, so a
chunk of C positions is no masked product of decay tiles: the u of one
position depends on the u of every earlier one. With G_i the running sum
of g inside the chunk (inclusive) and D_ij = exp(G_i - G_j), the paper's
WY form (its section 3.3) solves them together:

    A = strict_lower(beta_i (k_i . k_j) D_ij)     T = (I + A)^-1
    W = T (beta exp(G) * K)                       U = T (beta * V)
    V' = U - W S_prev                             (the chunk's u, [C, V])
    O  = (exp(G) * Q) S_prev + lower((q_i . k_j) D_ij) V'
    S_next = exp(G_C) S_prev + (exp(G_C - G) * K)^T V'

Every decay is exp of a number <= 0, masked BEFORE the exp. T is made by
doubling blocks, in float32 on the matrix unit (`_unit_lower_inverse`):
the inverses of the diagonal blocks of size m give those of size 2m,
[[T1, 0], [-T2 A21 T1, T2]], two products a level, ten a chunk of 64 and
no loop over rows; every step multiplies blocks of the inverse itself, so
it is as well conditioned as the answer (the product of powers
(I - A)(I + A^2)(I + A^4)..., as many products, is not: there it says
why). T enters W and U as I + (T - I) so that only the part off the
diagonal is rounded to the operands' dtype.

Two kernels, forward and backward, each one `pallas_call` on a grid
(batch, chunks) whose chunk axis is sequential: the states ride a float32
output block that stays in VMEM from chunk to chunk (backward: their
gradients, from the last chunk to the first). A program works ALL heads
of one chunk, slicing each head's columns out of the projections' own
layout [batch, seq, heads * width]: no transposed copy, and no head count
or width has to fall on the chip's 128-lane tiles (30 heads of 96 and 192
do not: a head's columns are cut out of one or two tiles where it is
read, nothing is padded in HBM). The forward kernel walks the heads by
the 128-lane tile up to T and one at a time after it: wherever two
chunks' widths fit the lanes (`heads_per_tile`: two at chunks of 64, one
at 128) two heads' [C, C] float32 tiles stand side by side in one
[C, 2C] tile, so D is one exponential, A one mask and the inverse one
chain of products a pair (each product the pair's tile times the pair's
tile laid on the diagonal of a [2C, 2C] one: what a head's sums gain
from its neighbour are exact zeros), fifteen chains a chunk of thirty
heads where thirty ran, an odd count's last head alone; W, U, V', O and
S1, whose operands are K and V wide, and the whole backward kernel work a
head at a time. T is made once a head and chunk, by the forward kernel: a
forward that will be differentiated (`_rule_fwd`) leaves T - I in HBM as
it enters W and U, rounded to the operands' dtype, [chunks, chunk,
heads * chunk] (the heads side by side along the lanes, two to a 128-lane
tile, a pair's in one whole store: 63 MB a layer at Olmo-Hybrid-7B's
shape), beside the state entering each chunk, [chunks, heads, K, V]
float32 (never one a token; 566 MB). The backward kernel reads both and
makes again only what is cheap, in bfloat16: D, k k^T, W, U, V', q k^T and
the two tiles cut from them (five products a head and chunk; the
inverse's float32 ones run in the forward alone). The other [C, C]
tiles, W, U and V' live in VMEM only, in both passes. The call that is
not differentiated (`_rule`: prefill, `cached_forward`) writes no T. The
gradient by the decays comes from the same float32 tiles by row and by
column, so the running sums' reverse cumulative sum adds rectangle sums of
one tile and subtracts nothing it did not add (ops/ssm_scan.py tells why
that matters).

`gated_delta_plan` gives the sizes from the shape and counts what runs.
The jax form `gated_delta_reference` serves other backends, lengths that
are no whole number of chunks and the tests; RAY_TPU_PALLAS_INTERPRET=1
runs the kernels in interpreter mode on the CPU (ops/attention.py
`_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention
from .attention import DEFAULT_MASK_VALUE, _NN, _NT, _dot

_TN = (((0,), (0,)), ((), ()))      # a.T @ b
LANES = 128                         # of a vector register and of a tile
# All heads of a chunk are in VMEM at once, with a state block each way:
# more than the 32 MiB the attention kernels are planned against, of the
# chip's 128.
VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# Reference: the same chunked mathematics in plain jax.numpy
# ---------------------------------------------------------------------------
def _chunk_sums(g, chunk: int):
    """Running sum of g inside each chunk: [b, L, H] float32."""
    b, L, H = g.shape
    return jnp.cumsum(g.reshape(b, L // chunk, chunk, H), axis=2).reshape(
        b, L, H)


def gated_delta_reference(q, k, v, g, beta, chunk: int = 64,
                          initial_state=None):
    """Plain XLA chunked delta rule; any length (the tail is padded with
    g = 0 and beta = 0, which leaves the state as it is). Float32 inside,
    T by a triangular solve; o comes back in v's dtype, the state in
    float32."""
    from jax.scipy.linalg import solve_triangular

    b, L, H, K = q.shape
    V = v.shape[-1]
    pad = -L % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (L + pad) // chunk
    f32 = jnp.float32

    def by_chunk(t):            # [b, L, H, ...] -> [nc, b, H, chunk, ...]
        t = t.astype(f32).reshape(b, nc, chunk, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    bc = by_chunk(beta)[..., None]                          # [nc,b,H,C,1]
    cum = by_chunk(_chunk_sums(g.astype(f32), chunk))       # [nc,b,H,C]
    seg = cum[..., :, None] - cum[..., None, :]
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols, seg, DEFAULT_MASK_VALUE))
    A = jnp.where(rows > cols,
                  bc * jnp.einsum("...ik,...jk->...ij", kc, kc) * decay, 0.0)
    eg = jnp.exp(cum)[..., None]
    rhs = jnp.concatenate([bc * eg * kc, bc * vc], axis=-1)
    WU = solve_triangular(A + jnp.eye(chunk, dtype=f32), rhs, lower=True,
                          unit_diagonal=True)
    W, U = WU[..., :K], WU[..., K:]
    P = jnp.einsum("...ik,...jk->...ij", qc, kc) * decay
    end = cum[..., -1:]                                     # [nc,b,H,1]
    to_end = jnp.exp(end - cum)[..., None]

    def carry(S, c):
        W_c, U_c, P_c, q_c, k_c, eg_c, to_end_c, end_c = c
        Vp = U_c - jnp.einsum("bhik,bhkv->bhiv", W_c, S)
        o = (eg_c * jnp.einsum("bhik,bhkv->bhiv", q_c, S)
             + jnp.einsum("bhij,bhjv->bhiv", P_c, Vp))
        S = (jnp.exp(end_c)[..., None] * S
             + jnp.einsum("bhik,bhiv->bhkv", to_end_c * k_c, Vp))
        return S, o

    S0 = jnp.zeros((b, H, K, V), f32) if initial_state is None \
        else initial_state.astype(f32)
    final, o = jax.lax.scan(carry, S0, (W, U, P, qc, kc, eg, to_end, end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, nc * chunk, H, V)
    return o[:, :L].astype(v.dtype), final


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GatedDeltaPlan:
    """Sizes of one gated_delta_rule call and what a sequence of one batch
    row executes. `grid` is the chunks a batch row; a grid program works
    all `heads_per_block` = heads of one chunk, the forward
    `heads_per_tile` of them side by side up to T (two where two chunks'
    widths fit the 128 lanes, an odd count's last head alone) and one at a
    time after it, the backward one at a time. A head's K columns are read
    out of `key_tile` lanes of VMEM and its V columns out of `value_tile`
    (the widths rounded up to whole 128-lane tiles: 96 -> 128, 192 -> 256;
    HBM holds the widths as they are). `fwd_matmuls` and `bwd_matmuls`
    count the products a pass runs on the matrix unit, `inverse_matmuls`
    those of the forward's that make T in float32, two a level and tile
    of heads (the backward makes none: it reads the T - I a
    differentiated forward leaves in HBM, `kept_bytes` a call at two bytes
    a value); `fwd_exps` and `bwd_exps` the exponentials' tiles, the
    forward's [chunk, heads_per_tile * chunk], the backward's [chunk,
    chunk] (the [chunk, 1] columns beside them are not counted). At
    Olmo-Hybrid-7B's shape, `gated_delta_plan(16384, 30, 96, 192, 64)`:
    `heads_per_tile` 2, `inverse_matmuls` 38,400 (76,800 a head at a
    time), `fwd_matmuls` 99,840, `fwd_exps` 3,840, `bwd_matmuls` 161,280,
    `bwd_exps` 7,680."""
    seq_len: int
    chunk: int
    chunks: int
    heads_per_block: int
    heads_per_tile: int
    grid: tuple
    key_tile: int
    value_tile: int
    vmem_bytes: int                # the backward kernel's, the larger
    state_bytes: int               # the chunk states in HBM, one way
    kept_bytes: int                # T - I a head and chunk, bfloat16
    inverse_matmuls: int
    fwd_matmuls: int
    bwd_matmuls: int
    fwd_exps: int
    bwd_exps: int


def heads_per_tile(chunk: int) -> int:
    """Heads whose [chunk, chunk] float32 tiles the forward works side by
    side up to T: two where two chunks' widths fit the 128 lanes."""
    return 2 if 2 * chunk <= LANES else 1


def _inverse_levels(chunk: int) -> int:
    """Levels of `_unit_lower_inverse` that multiply: block sizes 2, 4,
    ... under `chunk` (two products each)."""
    return max(0, (chunk - 1).bit_length() - 1)


# What one head of one chunk runs besides T, by `_tile_forward` and
# `_head_backward` below: the forward's eight, the five of them the
# backward makes again (`_head_tiles`, `_head_chunk`: all but O's two and
# S1's), and its own sixteen.
_FWD_PRODUCTS = 8
_AGAIN_PRODUCTS = 5
_BWD_PRODUCTS = 16


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _vmem_bytes(heads: int, key_dim: int, value_dim: int, chunk: int) -> int:
    """What the backward kernel holds: double-buffered blocks (q, k, dq,
    dk and v, dO, dv and the heads' T - I in bf16; three state blocks),
    the eight [C, heads] columns and rows, and a head's float32
    temporaries."""
    kt, vt = _round_up(key_dim, LANES), _round_up(value_dim, LANES)
    state = heads * _round_up(key_dim, 8) * vt * 4
    acts = chunk * heads * (4 * key_dim + 3 * value_dim + chunk) * 2
    return (2 * acts + 2 * 3 * state + 8 * 2 * chunk * LANES * 4
            + 12 * chunk * (kt + vt) * 4 + 16 * chunk * chunk * 4)


def gated_delta_plan(seq_len: int, heads: int, key_dim: int, value_dim: int,
                     chunk: int) -> GatedDeltaPlan:
    """The tiling `gated_delta_rule` runs a [.., seq_len, heads, .] call
    at. The kernels take their sizes from here, so what it reports is what
    runs."""
    if seq_len % chunk:
        raise ValueError(f"the kernels work whole chunks of {chunk}, not "
                         f"{seq_len} positions")
    need = _vmem_bytes(heads, key_dim, value_dim, chunk)
    if need > VMEM_LIMIT:
        raise ValueError(
            f"gated_delta: chunks of {chunk} with {heads} heads of "
            f"{key_dim} x {value_dim} do not fit {VMEM_LIMIT} bytes of VMEM")
    chunks = seq_len // chunk
    per_tile = heads_per_tile(chunk)
    tiles = chunks * -(-heads // per_tile)
    inverse = tiles * 2 * _inverse_levels(chunk)
    return GatedDeltaPlan(
        seq_len=seq_len, chunk=chunk, chunks=chunks, heads_per_block=heads,
        heads_per_tile=per_tile, grid=(chunks,),
        key_tile=_round_up(key_dim, LANES),
        value_tile=_round_up(value_dim, LANES), vmem_bytes=need,
        state_bytes=chunks * heads * key_dim * value_dim * 4,
        kept_bytes=chunks * heads * chunk * chunk * 2,
        inverse_matmuls=inverse,
        fwd_matmuls=inverse + chunks * heads * _FWD_PRODUCTS,
        bwd_matmuls=chunks * heads * (_AGAIN_PRODUCTS + _BWD_PRODUCTS),
        fwd_exps=tiles, bwd_exps=chunks * heads)


def _kernel_ok(q, chunk: int) -> bool:
    """Whether the kernels run this call: on a TPU (or interpreted), whole
    chunks, and on the chip chunks of whole bfloat16 tiles' rows."""
    if not attention._on_tpu() or q.shape[1] % chunk:
        return False
    return attention._interpret() or chunk % 16 == 0


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
def _tile_indices(chunk: int, n: int):
    """(rows, cols) of a [C, n * C] tile that holds n heads' [C, C] tiles
    side by side along the lanes: `cols` counts inside a head."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, n * chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, n * chunk), 1)
    return rows, lane if n == 1 else lane % chunk


def _side_by_side(cols, chunk: int):
    """[C, 1] columns, one a head, spread over their heads' lanes of a
    [C, n * C] tile; one column broadcasts by itself."""
    wide = cols[-1]
    if len(cols) > 1:
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (chunk, len(cols) * chunk), 1)
        for i in reversed(range(len(cols) - 1)):
            wide = jnp.where(lane < (i + 1) * chunk, cols[i], wide)
    return wide


def _unit_lower_inverse(A, chunk: int):
    """(I + A)^-1 - I for strictly lower triangular [C, C] float32
    matrices, n of them side by side along the lanes in A [C, n * C] (two
    fill a 128-lane tile at chunks of 64), by doubling blocks: the inverse
    of the diagonal blocks of size m gives that of size 2m, [[T1, 0],
    [-T2 A21 T1, T2]], for m = 1 (the identity), 2, 4, ...: with A_m the
    entries of A that join the two halves of a 2m-block, T <- T - T A_m T,
    two float32 products on the matrix unit a level and none at the first.
    The n heads share each product: the left operand is their tile as it
    stands, the right one their tile laid on the diagonal of an [n * C,
    n * C] tile, so what one head's sums gain from another's are exact
    zeros and each head's T is the number it is alone. Every step
    multiplies blocks of the inverse itself, so nothing grows that the
    answer does not hold. (The product (I - A)(I + A^2)(I + A^4)... is as
    many products and was the first form here: with keys that all lean one
    way, as silu leaves them, A ~ c L and A^k reaches c^k binom(C, k), 2e4
    at c = 0.24 and 1e17 at c = 1, against an inverse of entries under c:
    it lost the digits it had, and the cell's loss was NaN within a
    window.)"""
    n = A.shape[1] // chunk
    rows, cols = _tile_indices(chunk, n)
    lane = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    own = [(lane >= i * chunk) & (lane < (i + 1) * chunk) for i in range(n)]

    def mm(a, b):
        return jax.lax.dot_general(a, b, _NN,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    def on_diagonal(x):     # [C, n * C] -> [n * C, n * C], zeros elsewhere
        if n == 1:
            return x
        return jnp.concatenate([jnp.where(head, x, 0.0) for head in own],
                               axis=0)

    def joins(log_m):   # same 2m-block, different m-blocks (rows > cols)
        return ((rows >> (log_m + 1) == cols >> (log_m + 1))
                & (rows >> log_m != cols >> log_m))

    T = -jnp.where(joins(0), A, 0.0)                    # T - I at m = 2
    for log_m in range(1, _inverse_levels(chunk) + 1):
        A_m = jnp.where(joins(log_m), A, 0.0)
        # (I + T) A_m (I + T), the identity's parts added, not multiplied
        TA = A_m + mm(T, on_diagonal(A_m))
        T = T - TA - mm(TA, on_diagonal(T))
    return T


def _times_last(x, col):
    """x [n, m] times the last entry of col [C, 1]. Mosaic broadcasts
    along the lanes or along the sublanes, never a [1, 1] along both (and
    folds a slice of a broadcast back into one), so the column is spread
    along the lanes and its last row taken by a masked sum over rows."""
    C = col.shape[0]
    last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    wide = jnp.where(last, jnp.broadcast_to(col, (C, x.shape[1])), 0.0)
    return x * jnp.sum(wide, axis=0, keepdims=True)


def _head_tiles(ks, gcs, gr, chunk: int):
    """The [C, C] float32 tiles of one chunk that both passes make, for
    the n heads of `ks` side by side in [C, n * C] tiles (the forward: the
    heads of a 128-lane tile; the backward: one head): (rows, cols inside a
    head, D_ij = exp(G_i - G_j) on and under the diagonal, KK = k k^T).
    `gcs` the heads' running sums by row, [C, 1] each; `gr` [1, n * C]
    theirs by column, side by side."""
    rows, cols = _tile_indices(chunk, len(ks))
    D = jnp.exp(jnp.where(rows >= cols, _side_by_side(gcs, chunk) - gr,
                          DEFAULT_MASK_VALUE))
    KK = jnp.concatenate([_dot(k, k, _NT) for k in ks], axis=1)
    return rows, cols, D, KK


def _head_chunk(q, k, v, gc, bc, S0, D, Tm):
    """One head of one chunk up to V', from the state entering and T: what
    the forward makes once and the backward again (with `_head_tiles`'
    product, five bfloat16 products). q, k [C, K], v [C, V] and Tm = T - I
    [C, C] in the model's dtype; gc, bc [C, 1] the running sums of g and
    beta, D [C, C] float32; S0 [K, V] float32."""
    dtype, f32 = q.dtype, jnp.float32
    chunk = q.shape[0]
    k32, v32 = k.astype(f32), v.astype(f32)
    eg = jnp.exp(gc)
    end = gc[chunk - 1:chunk, :]                            # [1, 1]
    to_end, exp_end = jnp.exp(end - gc), jnp.exp(end)
    Kbg, Vb = k32 * (bc * eg), v32 * bc
    W = Kbg + _dot(Tm, Kbg.astype(dtype), _NN)
    U = Vb + _dot(Tm, Vb.astype(dtype), _NN)
    S0b = S0.astype(dtype)
    Vp = U - _dot(W.astype(dtype), S0b, _NN)
    QK = _dot(q, k, _NT)
    return dict(k32=k32, v32=v32, eg=eg, to_end=to_end, exp_end=exp_end,
                Kbg=Kbg, W=W, U=U, S0b=S0b, QK=QK, P=(QK * D).astype(dtype),
                Vpb=Vp.astype(dtype), Kd=(k32 * to_end).astype(dtype))


def _tile_forward(heads, gr, chunk: int):
    """The heads of one 128-lane tile (`heads_per_tile`: one or two) of
    one chunk. `heads`: a head's (q, k [C, K], v [C, V] in the model's
    dtype; gc [C, 1] the running sums of g by row and bc [C, 1] beta,
    float32; S0 [K, V] float32 the state entering); gr [1, n * C] the
    heads' running sums by column, side by side. Up to T the heads work
    as one tile: one exponential, one mask, one chain of the inverse's
    products. Returns (Tm = T - I in the model's dtype, [C, n * C], the
    one place T is made: the backward reads it; a head's (O [C, V]
    float32, S1))."""
    qs, ks, _, gcs, bcs, _ = zip(*heads, strict=True)
    rows, cols, D, KK = _head_tiles(ks, gcs, gr, chunk)
    A = jnp.where(rows > cols, _side_by_side(bcs, chunk) * KK * D, 0.0)
    Tm = _unit_lower_inverse(A, chunk).astype(qs[0].dtype)
    outs = []
    for i, (q, k, v, gc, bc, S0) in enumerate(heads):
        own = slice(i * chunk, (i + 1) * chunk)
        t = _head_chunk(q, k, v, gc, bc, S0, D[:, own], Tm[:, own])
        O = t["eg"] * _dot(q, t["S0b"], _NN) + _dot(t["P"], t["Vpb"], _NN)
        S1 = _times_last(S0, t["eg"]) + _dot(t["Kd"], t["Vpb"], _TN)
        outs.append((O, S1))
    return Tm, outs


def _gd_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref, init_ref,
                   o_ref, states_ref, final_ref, T_ref=None, *, H: int,
                   K: int, V: int):
    """`gr_ref`: the running sums by column of every head, [1, H * C], the
    heads side by side as `T_ref`'s are. `T_ref`: every head's T - I of the
    chunk, [C, H * C], where a backward pass will read it (`_rule_fwd`);
    the call that is not differentiated has no such output."""
    from jax.experimental import pallas as pl

    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        final_ref[...] = init_ref[...]

    gc_all, bc_all = gc_ref[0], bc_ref[0]
    n = heads_per_tile(chunk)
    for first in range(0, H, n):            # an odd count's last head alone
        tile = range(first, min(first + n, H))
        lanes = slice(first * chunk, tile.stop * chunk)
        heads = []
        for h in tile:
            ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
            S0 = final_ref[0, h]
            states_ref[0, 0, h] = S0
            heads.append((q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
                          gc_all[:, h:h + 1], bc_all[:, h:h + 1], S0))
        Tm, outs = _tile_forward(heads, gr_ref[0, 0, :, lanes], chunk)
        if T_ref is not None:
            T_ref[0, 0, :, lanes] = Tm
        for h, (O, S1) in zip(tile, outs, strict=True):
            o_ref[0, :, h * V:(h + 1) * V] = O.astype(o_ref.dtype)
            final_ref[0, h] = S1


def _head_backward(q, k, v, gc, gr, bc, S0, Tm, dO, dS1, chunk: int):
    """Every gradient of one head's work in one chunk: (dq, dk [C, K], dv
    [C, V], dbeta [C, 1], the running sums' gradient by row [C, 1] and by
    column [1, C], dS0 [K, V]), float32. W, U and V' are made again from
    S0 and the Tm = T - I the forward kept; no inverse is."""
    dtype, f32 = q.dtype, jnp.float32
    rows, cols, D, KK = _head_tiles([k], [gc], gr, chunk)
    t = _head_chunk(q, k, v, gc, bc, S0, D, Tm)
    eg, to_end, exp_end = t["eg"], t["to_end"], t["exp_end"]
    S0b, Vpb, k32 = t["S0b"], t["Vpb"], t["k32"]
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dS1b = dS1.astype(dtype)
    # through O and the state handed on
    dVp = _dot(t["P"], dO, _TN) + _dot(t["Kd"], dS1b, _NN)
    dQKD = _dot(dO, Vpb, _NT) * D            # D is 0 above the diagonal
    E = dQKD * t["QK"]
    dQg = _dot(dO, S0b, _NT)
    dQKDb = dQKD.astype(dtype)
    dq = eg * dQg + _dot(dQKDb, k, _NN)
    dk = _dot(dQKDb, q, _TN)
    dKd = _dot(Vpb, dS1b, _NT)
    dk += to_end * dKd
    held = rowsum(dKd * k32 * to_end)
    dg_col = rowsum(E) + rowsum(dQg * q.astype(f32)) * eg - held
    dg_row = -jnp.sum(E, axis=0, keepdims=True)
    at_end = (jnp.sum(held, axis=0, keepdims=True)
              + exp_end * jnp.sum(rowsum(dS1 * S0), axis=0, keepdims=True))
    dg_col += jnp.where(last_row, at_end, 0.0)
    dVpb = dVp.astype(dtype)
    dS0 = (_dot((q.astype(f32) * eg).astype(dtype), dO, _TN)
           + _times_last(dS1, eg)
           - _dot(t["W"].astype(dtype), dVpb, _TN))
    # through W, U and T: T^T applied as I + (T - I)^T
    dW = -_dot(dVpb, S0b, _NT)
    dKbg = dW + _dot(Tm, dW.astype(dtype), _TN)
    dVb = dVp + _dot(Tm, dVpb, _TN)
    dA = -jnp.where(rows > cols,
                    _dot(dKbg.astype(dtype), t["W"].astype(dtype), _NT)
                    + _dot(dVb.astype(dtype), t["U"].astype(dtype), _NT),
                    0.0)
    dAD = dA * D
    G = dAD * KK * bc
    dbeta = (rowsum(dAD * KK) + eg * rowsum(dKbg * k32)
             + rowsum(dVb * t["v32"]))
    dg_col += rowsum(G) + rowsum(dKbg * t["Kbg"])
    dg_row -= jnp.sum(G, axis=0, keepdims=True)
    M = (dAD * bc).astype(dtype)
    dk += _dot(M, k, _NN) + _dot(M, k, _TN) + (bc * eg) * dKbg
    return dq, dk, bc * dVb, dbeta, dg_col, dg_row, dS0


def _gd_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref, states_ref,
                   T_ref, do_ref, dfinal_ref, dq_ref, dk_ref, dv_ref,
                   dbeta_ref, dgc_ref, dgr_ref, dinit_ref, *, H: int, K: int,
                   V: int):
    from jax.experimental import pallas as pl

    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)         # chunks run last to first
    def _init():
        dinit_ref[...] = dfinal_ref[...]

    gc_all, gr_all, bc_all = gc_ref[0], gr_ref[0, 0], bc_ref[0]
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, H), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (H, chunk), 0)
    dbeta_tile = jnp.zeros((chunk, H), jnp.float32)
    dgc_tile = jnp.zeros((chunk, H), jnp.float32)
    dgr_tile = jnp.zeros((H, chunk), jnp.float32)
    for h in range(H):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        dq, dk, dv, dbeta, dg_col, dg_row, dS0 = _head_backward(
            q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
            gc_all[:, h:h + 1], gr_all[h:h + 1, :], bc_all[:, h:h + 1],
            states_ref[0, 0, h], T_ref[0, 0, :, h * chunk:(h + 1) * chunk],
            do_ref[0, :, vs], dinit_ref[0, h], chunk)
        dq_ref[0, :, ks] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, ks] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, vs] = dv.astype(dv_ref.dtype)
        dinit_ref[0, h] = dS0
        dbeta_tile = jnp.where(head_lane == h, dbeta, dbeta_tile)
        dgc_tile = jnp.where(head_lane == h, dg_col, dgc_tile)
        dgr_tile = jnp.where(head_row == h, dg_row, dgr_tile)
    dbeta_ref[0] = dbeta_tile
    dgc_ref[0] = dgc_tile
    dgr_ref[0, 0] = dgr_tile


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _by_row(cum, chunk: int):
    """[b, L, H] -> [b, chunks, H, chunk]: a chunk's sums along the lanes."""
    b, L, H = cum.shape
    return cum.reshape(b, L // chunk, chunk, H).transpose(0, 1, 3, 2)


def _specs(H: int, K: int, V: int, chunk: int, chunk_of):
    """BlockSpecs by role, on a grid (batch, chunk step); `chunk_of(step)`
    is the chunk a step works."""
    from jax.experimental import pallas as pl

    return dict(
        key=pl.BlockSpec((1, chunk, H * K), lambda i, s: (i, chunk_of(s), 0)),
        value=pl.BlockSpec((1, chunk, H * V),
                           lambda i, s: (i, chunk_of(s), 0)),
        col=pl.BlockSpec((1, chunk, H), lambda i, s: (i, chunk_of(s), 0)),
        row=pl.BlockSpec((1, 1, H, chunk),
                         lambda i, s: (i, chunk_of(s), 0, 0)),
        # the same sums, the heads side by side along the lanes as the
        # forward's tiles hold them
        rows=pl.BlockSpec((1, 1, 1, H * chunk),
                          lambda i, s: (i, chunk_of(s), 0, 0)),
        state=pl.BlockSpec((1, H, K, V), lambda i, s: (i, 0, 0, 0)),
        states=pl.BlockSpec((1, 1, H, K, V),
                            lambda i, s: (i, chunk_of(s), 0, 0, 0)),
        # a chunk's T - I, the heads side by side along the lanes: H * 64
        # columns fill whole 128-lane tiles two heads each, where a last
        # axis of 64 would be padded to twice its bytes in HBM
        inverse=pl.BlockSpec((1, 1, chunk, H * chunk),
                             lambda i, s: (i, chunk_of(s), 0, 0)))


# Jitted for the reason ops/attention.py's calls are: a model's layers
# trace and lower each kernel once a step, not once a layer.
@functools.partial(jax.jit, static_argnames=("chunk", "H", "keep_inverse"))
def _forward_call(q, k, v, cum, beta, init, *, chunk: int, H: int,
                  keep_inverse: bool):
    """q, k [b, L, H*K]; v [b, L, H*V]; cum, beta [b, L, H] f32; init
    [b, H, K, V] f32 -> (o like v, states [b, chunks, H, K, V] f32: the
    state ENTERING each chunk, the final state, and with `keep_inverse`
    every head's T - I [b, chunks, chunk, H * chunk] in q's dtype)."""
    from jax.experimental import pallas as pl

    b, L, HK = q.shape
    K, V, nc = HK // H, v.shape[-1] // H, L // chunk
    s = _specs(H, K, V, chunk, lambda step: step)
    out_specs = [s["value"], s["states"], s["state"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                 jax.ShapeDtypeStruct((b, nc, H, K, V), jnp.float32),
                 jax.ShapeDtypeStruct((b, H, K, V), jnp.float32)]
    if keep_inverse:
        out_specs.append(s["inverse"])
        out_shape.append(
            jax.ShapeDtypeStruct((b, nc, chunk, H * chunk), q.dtype))
    call = pl.pallas_call(
        functools.partial(_gd_fwd_kernel, H=H, K=K, V=V),
        grid=(b, nc),
        in_specs=[s["key"], s["key"], s["value"], s["col"], s["rows"],
                  s["col"], s["state"]],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("gated_delta_fwd"):
        return call(q, k, v, cum,
                    _by_row(cum, chunk).reshape(b, nc, 1, H * chunk), beta,
                    init)


@functools.partial(jax.jit, static_argnames=("chunk", "H"))
def _backward_call(q, k, v, cum, beta, states, inverse, do, dfinal, *,
                   chunk: int, H: int):
    """`states` and `inverse` as `_forward_call` left them -> (dq, dk, dv,
    dbeta, d cum [b, L, H] f32, d init)."""
    from jax.experimental import pallas as pl

    b, L, HK = q.shape
    K, V, nc = HK // H, v.shape[-1] // H, L // chunk
    s = _specs(H, K, V, chunk, lambda step: nc - 1 - step)
    col = jax.ShapeDtypeStruct((b, L, H), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_gd_bwd_kernel, H=H, K=K, V=V),
        grid=(b, nc),
        in_specs=[s["key"], s["key"], s["value"], s["col"], s["row"],
                  s["col"], s["states"], s["inverse"], s["value"],
                  s["state"]],
        out_specs=[s["key"], s["key"], s["value"], s["col"], s["col"],
                   s["row"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), col, col,
                   jax.ShapeDtypeStruct((b, nc, H, chunk), jnp.float32),
                   jax.ShapeDtypeStruct((b, H, K, V), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("gated_delta_bwd"):
        dq, dk, dv, dbeta, dgc, dgr, dinit = call(
            q, k, v, cum, _by_row(cum, chunk), beta, states, inverse, do,
            dfinal)
    dcum = dgc + dgr.transpose(0, 1, 3, 2).reshape(b, L, H)
    return dq, dk, dv, dbeta, dcum, dinit


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, initial_state=None):
    """The gated delta rule over a whole sequence.

    q, k [b, L, H, K], the keys (and as a rule the queries) L2-normalised
    by the caller; v [b, L, H, V]; g [b, L, H] <= 0 the log of each step's
    decay; beta [b, L, H]; `initial_state` [b, H, K, V] or None for zeros.
    Returns (o [b, L, H, V] in v's dtype, the final state [b, H, K, V]
    float32). Differentiable in everything but `chunk`: the kernels on a
    TPU, `gated_delta_reference` elsewhere."""
    b, L, H, K = q.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, H, K, v.shape[-1]), jnp.float32)
    return _rule(q, k.astype(q.dtype), v.astype(q.dtype),
                 g.astype(jnp.float32), beta.astype(jnp.float32),
                 initial_state.astype(jnp.float32), chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _rule(q, k, v, g, beta, init, chunk):
    """The call that is not differentiated (prefill, `cached_forward`): no
    backward pass will read T, so the kernel writes none."""
    if not _kernel_ok(q, chunk):
        return gated_delta_reference(q, k, v, g, beta, chunk, init)
    o, _, final = _run_forward(q, k, v, g, beta, init, chunk,
                               keep_inverse=False)
    return o.reshape(v.shape), final


def _run_forward(q, k, v, g, beta, init, chunk, keep_inverse: bool):
    """`_forward_call` on the projections' own layout: (o [b, L, H*V],
    states, final, and with `keep_inverse` T - I)."""
    b, L, H, K = q.shape
    V = v.shape[-1]
    gated_delta_plan(L, H, K, V, chunk)          # refuses what does not fit
    return _forward_call(
        q.reshape(b, L, H * K), k.reshape(b, L, H * K),
        v.reshape(b, L, H * V), _chunk_sums(g, chunk), beta, init,
        chunk=chunk, H=H, keep_inverse=keep_inverse)


@jax.named_scope("gated_delta_fwd")
def _rule_fwd(q, k, v, g, beta, init, chunk):
    if not _kernel_ok(q, chunk):
        out = gated_delta_reference(q, k, v, g, beta, chunk, init)
        return out, (q, k, v, g, beta, init, None, None)
    o, states, final, inverse = _run_forward(q, k, v, g, beta, init, chunk,
                                             keep_inverse=True)
    # What the forward kernel made and a backward pass reads, by name: the
    # states and T - I are the backward kernel's, o the gated norm's after
    # it. A rematerialised block keeps all three and the forward kernel
    # runs once (models/decoder.py KEPT_UNDER_REMAT).
    o = checkpoint_name(o, "gated_delta_o")
    states = checkpoint_name(states, "gated_delta_states")
    inverse = checkpoint_name(inverse, "gated_delta_T")
    return (o.reshape(v.shape), final), (q, k, v, g, beta, init, states,
                                         inverse)


@jax.named_scope("gated_delta_bwd")
def _rule_bwd(chunk, residuals, cotangents):
    q, k, v, g, beta, init, states, inverse = residuals
    do, dfinal = cotangents
    if states is None:
        _, vjp = jax.vjp(
            lambda *args: gated_delta_reference(*args[:5], chunk, args[5]),
            q, k, v, g, beta, init)
        return vjp((do, dfinal))
    b, L, H, K = q.shape
    V = v.shape[-1]
    cum, cum_vjp = jax.vjp(lambda g_: _chunk_sums(g_, chunk), g)
    dq, dk, dv, dbeta, dcum, dinit = _backward_call(
        q.reshape(b, L, H * K), k.reshape(b, L, H * K),
        v.reshape(b, L, H * V), cum, beta, states, inverse,
        do.reshape(b, L, H * V).astype(v.dtype), dfinal.astype(jnp.float32),
        chunk=chunk, H=H)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            cum_vjp(dcum)[0], dbeta, dinit)


_rule.defvjp(_rule_fwd, _rule_bwd)
