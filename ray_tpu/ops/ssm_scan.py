"""Chunked selective scan (Mamba-2's SSD): Pallas TPU kernels + jax reference.

The recurrence, per head (x_t in R^P, B_t, C_t in R^N, dt_t > 0, a < 0):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t          S in R^{P x N}
    y_t = S_t C_t + D x_t

run in chunks of Q positions. With L_i the running sum of dt a inside a
chunk (inclusive), the chunk's own part of y is
((C B^T) * exp(L_i - L_j)[i >= j]) (dt x), the part from earlier chunks
exp(L_i) C_i S_prev, and the state passes on as
S <- exp(L_end) S + sum_j exp(L_end - L_j) dt_j x_j (x) B_j. Every decay is
exp of a number <= 0: the difference is masked BEFORE the exp, so nothing
overflows however strong the decay.

Two kernels, forward and backward, each one `pallas_call` on a grid
(batch, chunks, head blocks) whose chunk axis is sequential: the states
ride a float32 VMEM scratch from chunk to chunk (backward: their
gradients, from the last chunk to the first). The [Q, Q] decay and score
tiles live in VMEM only, in both passes (the backward makes them again);
what reaches HBM is x, dt, the running sums, B, C, y, their gradients, and
one state a chunk [chunks, heads, N, P] that the forward writes and the
backward reads. C B^T is one product a chunk for all the heads of a
group (B and C come in G groups, head h reading group h // (H / G); a
block of heads never straddles two, so a grid program reads one group's B
and C block), and the gradients of B and C are summed over a group's
heads inside the kernel.
The gradient by the decays is never a difference of large sums: the
chunk's own scores give it step by step as rectangle sums of one tile
(`_ssm_bwd_kernel`), and only what goes through the states comes by the
running sums (dy . y less x . dx by position, the first form, was exact
in float32 and off by 20-100% in the decay rates' gradient on the chip,
where the products' operands are bfloat16; PERF.md §6, PR 29).

Heads are worked in pairs: two heads' P = 64 columns fill the 128 lanes
of a tile, B and C are shared, so the state update, the read of the
carried state and the state's gradients are one matmul a pair, and only
the [Q, Q] tiles are made per head. Activations keep the projection's own
layout [batch, seq, heads * P]: no transposed copy is made.

`ssm_scan_plan` gives the sizes from the shape and counts what runs. The
jax form `ssm_scan_reference` serves other backends, shapes the kernels
do not tile and the tests; RAY_TPU_PALLAS_INTERPRET=1 runs the kernels
in interpreter mode on the CPU (ops/attention.py `_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention
from .attention import DEFAULT_MASK_VALUE, VMEM_BUDGET, _NN, _NT, _dot


# ---------------------------------------------------------------------------
# Reference: the same chunked mathematics in plain jax.numpy
# ---------------------------------------------------------------------------
def _chunk_sums(dt, a, chunk: int):
    """Running sum of dt a inside each chunk: [b, L, H] float32."""
    b, L, H = dt.shape
    da = (dt * a).reshape(b, L // chunk, chunk, H)
    return jnp.cumsum(da, axis=2).reshape(b, L, H)


def ssm_scan_reference(x, dt, a, B, C, D, chunk: int, initial_state=None):
    """Plain XLA chunked scan; any length (the tail is padded with dt = 0,
    which leaves the state as it is) and any number of groups. Float32
    inside; y comes back in x's dtype, the state in float32."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    pad = -L % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, B, C))
    nc = (L + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(b, nc, chunk, H, P)
    Bh = jnp.repeat(B.astype(f32), H // G, axis=2).reshape(b, nc, chunk, H, N)
    Ch = jnp.repeat(C.astype(f32), H // G, axis=2).reshape(b, nc, chunk, H, N)
    cum = _chunk_sums(dt, a.astype(f32), chunk).reshape(b, nc, chunk, H)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [b,nc,i,j,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, seg, DEFAULT_MASK_VALUE))
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Ch, Bh) * decay
    y_own = jnp.einsum("bcijh,bcjhp->bcihp", scores, xd)
    end = cum[:, :, -1]                                      # [b,nc,H]
    own = jnp.einsum("bcjh,bcjhp,bcjhn->bchpn",
                     jnp.exp(end[:, :, None] - cum), xd, Bh)

    def carry(S, inputs):
        own_c, end_c = inputs
        return jnp.exp(end_c)[..., None, None] * S + own_c, S
    S0 = jnp.zeros((b, H, P, N), f32) if initial_state is None \
        else initial_state.astype(f32)
    final, entering = jax.lax.scan(
        carry, S0, (own.transpose(1, 0, 2, 3, 4), end.transpose(1, 0, 2)))
    y_prev = jnp.einsum("bcih,bcihn,cbhpn->bcihp", jnp.exp(cum), Ch, entering)
    y = (y_own + y_prev).reshape(b, nc * chunk, H, P)[:, :L]
    y = y + D.astype(f32)[:, None] * x[:, :L].astype(f32)
    return y.astype(x.dtype), final


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
_MAX_HEADS_PER_BLOCK = 8


@dataclasses.dataclass(frozen=True)
class SsmScanPlan:
    """Sizes of one ssm_scan call and what a sequence of one batch row
    executes. `grid` is (chunks, head blocks) a batch row; a grid program
    works `heads_per_block` heads, in pairs, on one chunk. `fwd_tiles` and
    `bwd_tiles` count the [chunk, chunk] decay tiles a pass builds
    (forward one a head and chunk; backward two, the tile and its
    transpose)."""
    seq_len: int
    chunk: int
    chunks: int
    heads_per_block: int
    grid: tuple
    vmem_bytes: int                # the backward kernel's, the larger
    state_bytes: int               # the chunk states in HBM, one way
    fwd_tiles: int
    bwd_tiles: int
    groups: int = 1                # of B and C; a block lies inside one


def _vmem_bytes(hb: int, heads: int, head_dim: int, d_state: int,
                chunk: int) -> int:
    """What the backward kernel holds at `hb` heads a block:
    double-buffered blocks (x, dy, dx in bf16; four state blocks of a
    block's pairs), the state scratch of all pairs, four [Q, Q] scratch
    tiles and eight temporaries, the [Q, N] blocks and accumulators."""
    Q, P2, N = chunk, 2 * head_dim, d_state
    return (2 * 3 * Q * hb * head_dim * 2 + 2 * 4 * (hb // 2) * N * P2 * 4
            + (heads // 2) * N * P2 * 4 + 12 * Q * Q * 4 + 8 * Q * N * 4)


def ssm_scan_plan(seq_len: int, heads: int, head_dim: int, d_state: int,
                  chunk: int, groups: int = 1) -> SsmScanPlan:
    """The tiling `ssm_scan` runs a [.., seq_len, heads, head_dim] call at,
    B and C in `groups` groups. The kernels take their sizes from here, so
    what it reports is what runs. A block is the largest even number of
    heads up to 8 that divides a group's heads and whose estimate fits
    VMEM_BUDGET."""
    if seq_len % chunk or heads % groups or (heads // groups) % 2:
        raise ValueError(
            f"the kernels work whole chunks of {chunk} and pairs of heads "
            f"inside a group, not {seq_len} positions of {heads} heads in "
            f"{groups} group(s)")
    fits = [h for h in range(2, _MAX_HEADS_PER_BLOCK + 1, 2)
            if (heads // groups) % h == 0 and _vmem_bytes(
                h, heads, head_dim, d_state, chunk) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"ssm_scan: chunks of {chunk} with {heads} heads of {head_dim} "
            f"and state {d_state} do not fit {VMEM_BUDGET} bytes of VMEM")
    hb, chunks = max(fits), seq_len // chunk
    return SsmScanPlan(
        seq_len=seq_len, chunk=chunk, chunks=chunks, heads_per_block=hb,
        grid=(chunks, heads // hb),
        vmem_bytes=_vmem_bytes(hb, heads, head_dim, d_state, chunk),
        state_bytes=chunks * (heads // 2) * d_state * 2 * head_dim * 4,
        fwd_tiles=chunks * heads, bwd_tiles=2 * chunks * heads,
        groups=groups)


def _kernel_ok(x, B, chunk: int) -> bool:
    """Whether the kernels run this call: on a TPU (or interpreted), whole
    chunks, groups of whole pairs of heads, and on the chip tiles of 128."""
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    if not attention._on_tpu() or L % chunk or H % G or (H // G) % 2:
        return False
    return attention._interpret() or (
        chunk % 128 == 0 and (2 * P) % 128 == 0 and N % 128 == 0)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
def _pairing(Q: int, P: int):
    """For a pair of heads side by side in 2P lanes: (left [Q, 2P] mask of
    the first head's lanes, pair(v, h) = column h of v [Q or 1, hb] over
    the first head's lanes and column h + 1 over the second's)."""
    left = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * P), 1) < P

    def pair(v, h):
        return jnp.where(left[:v.shape[0]], v[:, h:h + 1], v[:, h + 1:h + 2])
    return left, pair


def _decay(lc, lr, h: int, keep):
    """exp(L_row - L_col) where `keep`, 0 elsewhere: masked before the
    exp. lc [Q, hb] holds the sums by row, lr [hb, Q] by column."""
    return jnp.exp(jnp.where(keep, lc[:, h:h + 1] - lr[h:h + 1, :],
                             DEFAULT_MASK_VALUE))


def _group_edge(j, blocks: int, per_group: int, last: bool = False):
    """Whether head block `j` of `blocks` is the first (`last`: the last)
    of its group of `per_group` blocks."""
    edge = per_group - 1 if last else 0
    return j == edge if per_group == blocks else j % per_group == edge


def _ssm_fwd_kernel(x_ref, dt_ref, lc_ref, lr_ref, b_ref, bt_ref, c_ref,
                    init_ref, y_ref, states_ref, st_scr, cb_scr, *, P: int,
                    hb: int, blocks: int, per_group: int):
    from jax.experimental import pallas as pl

    c, j = pl.program_id(1), pl.program_id(2)
    Q = x_ref.shape[1]
    hp = hb // 2
    dtype = x_ref.dtype

    @pl.when(_group_edge(j, blocks, per_group))
    def _scores():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)

    @pl.when(c == 0)
    def _init():
        for p in range(hp):
            st_scr[j * hp + p] = init_ref[0, p]

    lc, dt, lr = lc_ref[0, 0], dt_ref[0, 0], lr_ref[0]
    end = lc[Q - 1:Q, :]                                   # [1, hb]
    exp_lc, to_end, exp_end = jnp.exp(lc), jnp.exp(end - lc), jnp.exp(end)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    left, pair = _pairing(Q, P)
    cb = cb_scr[...]
    for p in range(hp):
        h = 2 * p
        lanes = slice(p * 2 * P, (p + 1) * 2 * P)
        x2 = x_ref[0, :, lanes].astype(jnp.float32)
        xd = x2 * pair(dt, h)
        xd_op = xd.astype(dtype)
        own = [_dot((cb * _decay(lc, lr, h + k, rows >= cols)).astype(dtype),
                    xd_op, _NN) for k in (0, 1)]
        state = st_scr[j * hp + p]                         # [N, 2P] f32
        carried = pair(exp_lc, h) * _dot(c_ref[0], state.astype(dtype), _NN)
        y_ref[0, :, lanes] = (jnp.where(left, own[0], own[1])
                              + carried).astype(y_ref.dtype)
        new = pair(exp_end, h) * state + _dot(
            bt_ref[0], (xd * pair(to_end, h)).astype(dtype), _NN)
        st_scr[j * hp + p] = new
        states_ref[0, 0, p] = new


def _ssm_bwd_kernel(x_ref, dt_ref, lc_ref, lr_ref, b_ref, c_ref, ct_ref,
                    dy_ref, states_ref, init_ref, dfinal_ref,
                    dx_ref, ddt_ref, dl_ref, dda_ref, db_ref, dc_ref,
                    dinit_ref, dst_scr, cb_scr, cbt_scr, r_scr, dc_scr,
                    db_scr, *, P: int, hb: int, grid: tuple, per_group: int):
    """Every gradient of one chunk's work for a block of heads, chunks
    last to first. The gradient by the decays comes out in two parts that
    neither subtracts large sums that cancel: `dda`, by each step's own
    dt a, of the chunk's own scores (position k decays every pair j < k
    <= i, so it gets the sum of T_ij = dy_i . xd_j (C B^T)_ij M_ij over
    that rectangle, taken with one product by a triangle of ones); and
    `dl`, by the running sums, of what goes through the states."""
    from jax.experimental import pallas as pl

    step, j = pl.program_id(1), pl.program_id(2)
    first_chunk = step == grid[1] - 1        # chunks run last to first
    Q = x_ref.shape[1]
    hp = hb // 2
    dtype = x_ref.dtype

    @pl.when(_group_edge(j, grid[2], per_group))
    def _per_chunk():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)
        cbt_scr[...] = _dot(b_ref[0], c_ref[0], _NT)
        r_scr[...] = jnp.zeros_like(r_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    @pl.when(step == 0)
    def _init():
        for p in range(hp):
            dst_scr[j * hp + p] = dfinal_ref[0, p]

    lc, dt, lr = lc_ref[0, 0], dt_ref[0, 0], lr_ref[0]
    end = lc[Q - 1:Q, :]
    exp_lc, to_end, exp_end = jnp.exp(lc), jnp.exp(end - lc), jnp.exp(end)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    before = (rows < cols).astype(dtype)        # [j, k]: 1 where j < k
    last_row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (Q, hb), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hb, Q), 0)
    left, pair = _pairing(Q, P)
    cb, cbt = cb_scr[...], cbt_scr[...]
    ddt_tile = jnp.zeros((Q, hb), jnp.float32)
    dl_tile = jnp.zeros((Q, hb), jnp.float32)
    dda_tile = jnp.zeros((hb, Q), jnp.float32)

    def halves(v):
        """Sums over each head's lanes of v [Q or 1, 2P] -> two [., 1]."""
        first = jnp.sum(jnp.where(left[:v.shape[0]], v, 0.0), axis=1,
                        keepdims=True)
        return first, jnp.sum(v, axis=1, keepdims=True) - first

    for p in range(hp):
        h = 2 * p
        lanes = slice(p * 2 * P, (p + 1) * 2 * P)
        x2 = x_ref[0, :, lanes].astype(jnp.float32)
        dy_op = dy_ref[0, :, lanes]
        dy2 = dy_op.astype(jnp.float32)
        dt2 = pair(dt, h)
        xd = x2 * dt2
        xd_op = xd.astype(dtype)
        entering = jnp.where(first_chunk, init_ref[0, p], states_ref[0, 0, p])
        entering_op = entering.astype(dtype)
        dstate = dst_scr[j * hp + p]                       # [N, 2P] f32

        # d(dt x): the chunk's own part through the transposed tiles
        # (rows j, columns i), and the part through the state.
        own = [_dot((cbt * _decay_t(lc, lr, h + k, cols >= rows)
                     ).astype(dtype), dy_op, _NN) for k in (0, 1)]
        via_state = pair(to_end, h) * _dot(
            b_ref[0], dstate.astype(dtype), _NN)
        dxd = jnp.where(left, own[0], own[1]) + via_state
        dx_ref[0, :, lanes] = (dxd * dt2).astype(dx_ref.dtype)

        # dt's own gradient x . dxd, and the running sums' through the
        # states: by row what y reads of the entering state, by column
        # what the leaving state takes of dt x, and at the chunk's end
        # what the state's own decay carries.
        dy_scaled = dy2 * pair(exp_lc, h)
        s1 = halves(x2 * dxd)
        read = halves(dy_scaled * _dot(c_ref[0], entering_op, _NN))
        taken = halves(xd * via_state)
        held = halves(jnp.sum(dstate * entering, axis=0, keepdims=True))
        for k in (0, 1):
            at_end = (jnp.sum(taken[k], axis=0, keepdims=True)
                      + exp_end[:, h + k:h + k + 1] * held[k])
            dl = read[k] - taken[k] + jnp.where(last_row, at_end, 0.0)
            ddt_tile = jnp.where(head_lane == h + k, s1[k], ddt_tile)
            dl_tile = jnp.where(head_lane == h + k, dl, dl_tile)

        # d(C B^T), summed over the heads: M * (dy xd^T) a head; and the
        # step decays' gradient from the same tile.
        for k in (0, 1):
            mine = left if k == 0 else ~left
            t = _dot(jnp.where(mine, dy_op, jnp.zeros_like(dy_op)), xd_op,
                     _NT)
            r = _decay(lc, lr, h + k, rows >= cols) * t
            r_scr[...] += r
            upto = _dot((r * cb).astype(dtype), before, _NN)   # sum_{j<k}
            dda = jnp.sum(jnp.where(rows >= cols, upto, 0.0), axis=0,
                          keepdims=True)                       # sum_{i>=k}
            dda_tile = jnp.where(head_row == h + k, dda, dda_tile)

        dy_s = dy_scaled.astype(dtype)
        dc_scr[...] += _dot(dy_s, entering_op, _NT)
        db_scr[...] += _dot((xd * pair(to_end, h)).astype(dtype),
                            dstate.astype(dtype), _NT)
        new = pair(exp_end, h) * dstate + _dot(ct_ref[0], dy_s, _NN)
        dst_scr[j * hp + p] = new
        dinit_ref[0, p] = new

    ddt_ref[0, 0] = ddt_tile
    dl_ref[0, 0] = dl_tile
    dda_ref[0] = dda_tile

    @pl.when(_group_edge(j, grid[2], per_group, last=True))
    def _shared():
        r = r_scr[...]
        dc_ref[0] = (_dot(r.astype(dtype), b_ref[0], _NN)
                     + dc_scr[...]).astype(dc_ref.dtype)
        db_ref[0] = (_dot(r.T.astype(dtype), c_ref[0], _NN)
                     + db_scr[...]).astype(db_ref.dtype)


def _decay_t(lc, lr, h: int, keep):
    """The transpose of `_decay`'s tile: rows j, columns i."""
    return jnp.exp(jnp.where(keep, lr[h:h + 1, :] - lc[:, h:h + 1],
                             DEFAULT_MASK_VALUE))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


def _blocked(v, hb: int):
    """[b, L, H] -> [b, H // hb, L, hb]: a head block's columns from lane 0."""
    b, L, H = v.shape
    return v.reshape(b, L, H // hb, hb).transpose(0, 2, 1, 3)


def _unblocked(v):
    b, nhb, L, hb = v.shape
    return v.transpose(0, 2, 1, 3).reshape(b, L, nhb * hb)


def _to_pairs(state):
    """[b, H, P, N] -> [b, H/2, N, 2P] float32: a pair's states side by
    side, transposed (the kernels' layout)."""
    b, H, P, N = state.shape
    return state.astype(jnp.float32).reshape(b, H // 2, 2, P, N).transpose(
        0, 1, 4, 2, 3).reshape(b, H // 2, N, 2 * P)


def _from_pairs(pairs):
    b, hp, N, P2 = pairs.shape
    return pairs.reshape(b, hp, N, 2, P2 // 2).transpose(
        0, 1, 3, 4, 2).reshape(b, 2 * hp, P2 // 2, N)


def _specs(P: int, N: int, chunk: int, hb: int, chunk_of, per_group=None):
    """BlockSpecs by role, on a grid (batch, chunk step, head block);
    `chunk_of(step)` is the chunk a step works, `per_group` the head blocks
    that share a group's B and C (None: all of them, one group)."""
    from jax.experimental import pallas as pl

    hp, Q = hb // 2, chunk

    def group_of(j):
        return 0 if per_group is None else j // per_group

    return dict(
        act=pl.BlockSpec((1, Q, hb * P), lambda i, s, j: (i, chunk_of(s), j)),
        col=pl.BlockSpec((1, 1, Q, hb),
                         lambda i, s, j: (i, j, chunk_of(s), 0)),
        row=pl.BlockSpec((1, hb, Q), lambda i, s, j: (i, j, chunk_of(s))),
        bc=pl.BlockSpec((1, Q, N),
                        lambda i, s, j: (i, chunk_of(s), group_of(j))),
        bc_t=pl.BlockSpec((1, N, Q),
                          lambda i, s, j: (i, group_of(j), chunk_of(s))),
        state=pl.BlockSpec((1, hp, N, 2 * P), lambda i, s, j: (i, j, 0, 0)),
        states=pl.BlockSpec((1, 1, hp, N, 2 * P),
                            lambda i, s, j: (i, chunk_of(s), j, 0, 0)))


# Jitted for the reason ops/attention.py's calls are: a model's layers
# trace and lower each kernel once a step, not once a layer.
@functools.partial(jax.jit, static_argnames=("chunk", "hb", "groups"))
def _scan_forward_call(x, dt, cum, Bm, Cm, init, *, chunk: int, hb: int,
                       groups: int = 1):
    """x [b, L, H*P]; dt, cum [b, L, H] f32; Bm, Cm [b, L, G*N]; init
    [b, H/2, N, 2P] f32 -> (y like x, states [b, chunks, H/2, N, 2P] f32:
    the state leaving each chunk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, L, HP = x.shape
    H, N = dt.shape[-1], Bm.shape[-1] // groups
    P, nc = HP // H, L // chunk
    per_group = H // hb // groups
    s = _specs(P, N, chunk, hb, lambda step: step,
               None if groups == 1 else per_group)
    call = pl.pallas_call(
        functools.partial(_ssm_fwd_kernel, P=P, hb=hb, blocks=H // hb,
                          per_group=per_group),
        grid=(b, nc, H // hb),
        in_specs=[s["act"], s["col"], s["col"], s["row"], s["bc"],
                  s["bc_t"], s["bc"], s["state"]],
        out_specs=[s["act"], s["states"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, H // 2, N, 2 * P),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((H // 2, N, 2 * P), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    operands = (x, _blocked(dt, hb), _blocked(cum, hb),
                cum.transpose(0, 2, 1), Bm, Bm.transpose(0, 2, 1), Cm, init)
    with jax.named_scope("ssm_scan_fwd"):
        return call(*operands)


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "groups"))
def _scan_backward_call(x, dt, cum, Bm, Cm, init, states, dy, dfinal, *,
                        chunk: int, hb: int, groups: int = 1):
    """-> (dx, d dt (its own part), d cum (through the states), d (dt a)
    (the chunks' own scores), dB, dC, d init)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, L, HP = x.shape
    H, N = dt.shape[-1], Bm.shape[-1] // groups
    P, nc = HP // H, L // chunk
    grid = (b, nc, H // hb)
    per_group = H // hb // groups
    s = _specs(P, N, chunk, hb, lambda step: nc - 1 - step,
               None if groups == 1 else per_group)
    # The state entering a chunk is the one the chunk before it left.
    entering = pl.BlockSpec(
        (1, 1, hb // 2, N, 2 * P),
        lambda i, step, j: (i, jnp.maximum(nc - 2 - step, 0), j, 0, 0))
    col_shape = jax.ShapeDtypeStruct((b, H // hb, L, hb), jnp.float32)
    tile = pltpu.VMEM((chunk, chunk), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_ssm_bwd_kernel, P=P, hb=hb, grid=grid,
                          per_group=per_group),
        grid=grid,
        in_specs=[s["act"], s["col"], s["col"], s["row"], s["bc"], s["bc"],
                  s["bc_t"], s["act"], entering, s["state"], s["state"]],
        out_specs=[s["act"], s["col"], s["col"], s["row"], s["bc"], s["bc"],
                   s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), col_shape,
                   col_shape, jax.ShapeDtypeStruct((b, H, L), jnp.float32),
                   jax.ShapeDtypeStruct(Bm.shape, Bm.dtype),
                   jax.ShapeDtypeStruct(Cm.shape, Cm.dtype),
                   jax.ShapeDtypeStruct(init.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((H // 2, N, 2 * P), jnp.float32),
                        tile, tile, tile,
                        pltpu.VMEM((chunk, N), jnp.float32),
                        pltpu.VMEM((chunk, N), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    operands = (x, _blocked(dt, hb), _blocked(cum, hb),
                cum.transpose(0, 2, 1), Bm, Cm, Cm.transpose(0, 2, 1), dy,
                states, init, dfinal)
    with jax.named_scope("ssm_scan_bwd"):
        dx, ddt, dcum, dda, dB, dC, dinit = call(*operands)
    return (dx, _unblocked(ddt), _unblocked(dcum), dda.transpose(0, 2, 1),
            dB, dC, dinit)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def ssm_scan(x, dt, a, B, C, D, chunk: int, initial_state=None):
    """The selective scan of Mamba-2 over a whole sequence.

    x [b, L, H, P]; dt [b, L, H] the step sizes, positive (after the
    softplus); a [H] negative; B, C [b, L, G, N]; D [H]; `initial_state`
    [b, H, P, N] or None for zeros. Returns (y [b, L, H, P] in x's dtype,
    the final state [b, H, P, N] float32). Differentiable in everything
    but `chunk`: the kernels on a TPU, `ssm_scan_reference` elsewhere."""
    b, L, H, P = x.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    y, final = _scan(x, dt.astype(jnp.float32), a.astype(jnp.float32), B, C,
                     initial_state.astype(jnp.float32), chunk)
    skip = D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return (y.astype(jnp.float32) + skip).astype(x.dtype), final


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, B, C, init, chunk):
    return _scan_fwd(x, dt, a, B, C, init, chunk)[0]


def _groups_flat(t):
    """B or C [b, L, G, N] -> [b, L, G * N]: a group's N columns a block."""
    return t[:, :, 0] if t.shape[2] == 1 else t.reshape(*t.shape[:2], -1)


def _groups_apart(t, groups: int):
    """`_groups_flat` undone."""
    return t[:, :, None] if groups == 1 else t.reshape(
        *t.shape[:2], groups, -1)


def _zero_skip(x):
    return jnp.zeros((x.shape[2],), jnp.float32)


@jax.named_scope("ssm_scan_fwd")
def _scan_fwd(x, dt, a, B, C, init, chunk):
    if not _kernel_ok(x, B, chunk):
        out = ssm_scan_reference(x, dt, a, B, C, _zero_skip(x), chunk, init)
        return out, (x, dt, a, B, C, init, None)
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    plan = ssm_scan_plan(L, H, P, N, chunk, G)
    y, states = _scan_forward_call(
        x.reshape(b, L, H * P), dt, _chunk_sums(dt, a, chunk),
        _groups_flat(B), _groups_flat(C), _to_pairs(init), chunk=chunk,
        hb=plan.heads_per_block, groups=G)
    # What the forward kernel made and a backward pass reads, by name: the
    # states are the backward kernel's, y the gated norm's after it. A
    # rematerialised block keeps both and the forward kernel runs once
    # (models/decoder.py KEPT_UNDER_REMAT).
    y = checkpoint_name(y, "ssm_scan_y")
    states = checkpoint_name(states, "ssm_scan_states")
    return ((y.reshape(x.shape), _from_pairs(states[:, -1])),
            (x, dt, a, B, C, init, states))


@jax.named_scope("ssm_scan_bwd")
def _scan_bwd(chunk, residuals, cotangents):
    x, dt, a, B, C, init, states = residuals
    dy, dfinal = cotangents
    if states is None:
        _, vjp = jax.vjp(
            lambda *args: ssm_scan_reference(
                *args[:5], _zero_skip(x), chunk, args[5]),
            x, dt, a, B, C, init)
        return vjp((dy, dfinal))
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    plan = ssm_scan_plan(L, H, P, N, chunk, G)
    cum, cum_vjp = jax.vjp(lambda dt_, a_: _chunk_sums(dt_, a_, chunk), dt, a)
    dx, ddt, dcum, dda, dB, dC, dinit = _scan_backward_call(
        x.reshape(b, L, H * P), dt, cum, _groups_flat(B), _groups_flat(C),
        _to_pairs(init), states, dy.reshape(b, L, H * P).astype(x.dtype),
        _to_pairs(dfinal), chunk=chunk, hb=plan.heads_per_block, groups=G)
    ddt_sums, da = cum_vjp(dcum)
    return (dx.reshape(x.shape), ddt + ddt_sums + dda * a,
            da + jnp.sum(dda * dt, axis=(0, 1)), _groups_apart(dB, G),
            _groups_apart(dC, G), _from_pairs(dinit))


_scan.defvjp(_scan_fwd, _scan_bwd)
