"""Selective scan (Mamba-1's): Pallas TPU kernels + jax reference.

The recurrence, per channel c and state n (x_t, dt_t > 0 by channel; B_t,
C_t in R^N shared by all channels; A[c, n] < 0):

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    m_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]

Every (channel, state) pair has a decay of its own, so no part of it is a
matrix product (ops/ssm_scan.py's chunks of [Q, Q] matmuls need ONE decay
a head): the work is elementwise on the vector unit, one exponential a
pair and token, and the kernels are bound by that unit and the
exponential unit, not by the MXU.

Two kernels, forward and backward, each one `pallas_call` on a grid
(batch, chunks of `CHUNK` tokens, channel blocks) whose chunk axis is
sequential. A channel block is 8 x 128 channels, one float32 tile; a
program walks its chunk token by token with the block's N states, one
tile each, carried in registers; B_t[n] and C_t[n] are scalars read from
SMEM, so nothing is broadcast across lanes or reduced across them in the
loop. The state passes from chunk to chunk in a float32 VMEM scratch.
What reaches HBM: x, dt, m and their gradients [S, channels]; B, C
[S, N]; and ONE state a chunk [chunks, N, channels] float32, which the
forward writes and the backward reads. The backward makes the inside of a
chunk again from the state that entered it (into VMEM), then walks the
chunk backwards. Never [S, channels, N] in HBM.

The gradients by B and C are sums over all channels for every token and
state. A program adds its block's products into a VMEM tile a (token,
state), the last block of a chunk sums the tile's sublanes, and the 128
lanes that are left are summed by XLA: [S, N, 128] float32 leaves the
kernel and no reduction across lanes runs in it.

float32 for dt, the exponentials, the state and every sum; x, B, C and m
are bfloat16 outside (cast on the way in and out, by XLA, fused with what
made them). `selective_scan_plan` gives the sizes from the shape and
counts what runs. The jax form `selective_scan_reference` serves other
backends, shapes the kernels do not tile and the tests;
RAY_TPU_PALLAS_INTERPRET=1 runs the kernels in interpreter mode on the
CPU (ops/attention.py `_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention
from .attention import VMEM_BUDGET

CHUNK = 64          # tokens a grid step; one state a chunk is kept
_LANES = 128
_SUBLANES = 8


# ---------------------------------------------------------------------------
# Reference: the recurrence as it is defined, token by token
# ---------------------------------------------------------------------------
def selective_scan_reference(x, dt, A, B, C, D, initial_state=None):
    """Plain XLA `lax.scan` over the tokens, float32 inside. x, dt
    [b, S, channels]; A [channels, N]; B, C [b, S, N]; D [channels];
    `initial_state` [b, channels, N] or None for zeros. Returns (m like x,
    the final state float32)."""
    f32 = jnp.float32
    b, _, channels = x.shape
    A = A.astype(f32)

    def step(s, t):
        x_t, dt_t, B_t, C_t = t            # [b, c] [b, c] [b, N] [b, N]
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * x_t)[..., None] * B_t[:, None, :])
        return s, jnp.sum(s * C_t[:, None, :], axis=-1)

    s0 = jnp.zeros((b, channels, A.shape[1]), f32) \
        if initial_state is None else initial_state.astype(f32)
    final, ms = jax.lax.scan(step, s0, tuple(
        t.astype(f32).swapaxes(0, 1) for t in (x, dt, B, C)))
    m = ms.swapaxes(0, 1) + D.astype(f32) * x.astype(f32)
    return m.astype(x.dtype), final


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SelectiveScanPlan:
    """Sizes of one selective_scan call and what one batch row executes.
    `grid` is (chunks, channel blocks) a batch row; `block` the (sublanes,
    lanes) of channels a program holds. `exponentials`: one a (token,
    channel, state) forward, two backward (the chunk made again, then
    walked back). `state_bytes`: the chunk states in HBM, one way.
    `vmem_bytes`: the backward kernel's scratch, the larger."""
    seq_len: int
    padded_len: int
    chunk: int
    chunks: int
    block: tuple
    channel_blocks: int
    grid: tuple
    fwd_exponentials: int
    bwd_exponentials: int
    state_bytes: int
    vmem_bytes: int


def selective_scan_plan(seq_len: int, channels: int,
                        states: int) -> SelectiveScanPlan:
    """The tiling `selective_scan` runs a [.., seq_len, channels] call at.
    The kernels take their sizes from here, so what it reports is what
    runs. Channels lie 128 to a row; a block is 8 rows where the rows
    divide by 8, else all of them (small models, the tests). A length
    that is no whole number of chunks is padded with dt = 0, which leaves
    the state as it is."""
    if channels % _LANES:
        raise ValueError(
            f"the kernels lay channels 128 to a row, not {channels}")
    rows = channels // _LANES
    sub = _SUBLANES if rows % _SUBLANES == 0 else rows
    padded = -(-seq_len // CHUNK) * CHUNK
    chunks = padded // CHUNK
    tile = sub * _LANES * 4
    vmem = ((CHUNK + 1) * states * tile          # the chunk's states again
            + 2 * CHUNK * states * tile          # dB's and dC's products
            + 2 * 5 * CHUNK * tile               # x, dt, dm, dx, ddt twice
            + (rows // sub) * 2 * states * tile  # carried gradient, dA
            + 2 * 4 * states * tile)
    if vmem > VMEM_BUDGET:
        raise ValueError(
            f"selective_scan: {states} states of {sub * _LANES} channels "
            f"do not fit {VMEM_BUDGET} bytes of VMEM")
    per_pass = padded * channels * states
    return SelectiveScanPlan(
        seq_len=seq_len, padded_len=padded, chunk=CHUNK, chunks=chunks,
        block=(sub, _LANES), channel_blocks=rows // sub,
        grid=(chunks, rows // sub), fwd_exponentials=per_pass,
        bwd_exponentials=2 * per_pass,
        state_bytes=chunks * channels * states * 4, vmem_bytes=vmem)


def _kernel_ok(channels: int) -> bool:
    return attention._on_tpu() and channels % _LANES == 0


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------
def _selective_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, init_ref, m_ref,
                states_ref, st_scr, *, N: int):
    from jax.experimental import pallas as pl

    c, j = pl.program_id(1), pl.program_id(2)
    T = x_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        st_scr[j] = init_ref[0]

    a = [a_ref[n] for n in range(N)]

    def token(t, s):
        x, dt = x_ref[0, t], dt_ref[0, t]
        dtx = dt * x
        m = jnp.zeros_like(x)
        new = []
        for n in range(N):
            s_n = jnp.exp(dt * a[n]) * s[n] + dtx * b_ref[t, n]
            m = m + s_n * c_ref[t, n]
            new.append(s_n)
        m_ref[0, t] = m
        return tuple(new)

    s = jax.lax.fori_loop(0, T, token,
                          tuple(st_scr[j, n] for n in range(N)))
    for n in range(N):
        st_scr[j, n] = s[n]
        states_ref[0, 0, n] = s[n]


def _selective_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dm_ref, states_ref,
                init_ref, dfinal_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dinit_ref,
                s_scr, pb_scr, pc_scr, h_scr, da_scr, *, N: int,
                grid: tuple):
    """Every gradient of one chunk's work for a block of channels, chunks
    last to first. With g_t the gradient by s_t (what the later tokens
    hand back, h = exp(dt_{t+1} A) g_{t+1}, plus C_t dm_t):
    d(exp(dt A)) = g_t s_{t-1}, d(dt B x) = g_t."""
    from jax.experimental import pallas as pl

    step, j = pl.program_id(1), pl.program_id(2)
    first_chunk = step == grid[1] - 1        # chunks run last to first
    T = x_ref.shape[1]

    @pl.when(step == 0)
    def _init():
        h_scr[j] = dfinal_ref[0]
        da_scr[j] = jnp.zeros_like(da_scr[j])

    @pl.when(j == 0)
    def _per_chunk():
        pb_scr[...] = jnp.zeros_like(pb_scr)
        pc_scr[...] = jnp.zeros_like(pc_scr)

    a = [a_ref[n] for n in range(N)]

    # the states of this chunk again: s_scr[t] is the state BEFORE token t
    def token(t, s):
        x, dt = x_ref[0, t], dt_ref[0, t]
        dtx = dt * x
        new = []
        for n in range(N):
            s_scr[t, n] = s[n]
            new.append(jnp.exp(dt * a[n]) * s[n] + dtx * b_ref[t, n])
        return tuple(new)

    entering = jnp.where(first_chunk, init_ref[0], states_ref[0, 0])
    s = jax.lax.fori_loop(0, T, token,
                          tuple(entering[n] for n in range(N)))
    for n in range(N):
        s_scr[T, n] = s[n]

    def back(k, carry):
        h, da = carry
        t = T - 1 - k
        x, dt, dm = x_ref[0, t], dt_ref[0, t], dm_ref[0, t]
        dtx = dt * x
        q = jnp.zeros_like(x)
        r = jnp.zeros_like(x)
        new_h, new_da = [], []
        for n in range(N):
            g = h[n] + dm * c_ref[t, n]
            decay = jnp.exp(dt * a[n])
            via_decay = g * s_scr[t, n] * decay
            r = r + via_decay * a[n]
            new_da.append(da[n] + via_decay * dt)
            q = q + g * b_ref[t, n]
            pb_scr[t, n] += g * dtx
            pc_scr[t, n] += dm * s_scr[t + 1, n]
            new_h.append(decay * g)
        dx_ref[0, t] = dt * q
        ddt_ref[0, t] = x * q + r
        return tuple(new_h), tuple(new_da)

    h, da = jax.lax.fori_loop(
        0, T, back, (tuple(h_scr[j, n] for n in range(N)),
                     tuple(da_scr[j, n] for n in range(N))))
    for n in range(N):
        h_scr[j, n] = h[n]
        da_scr[j, n] = da[n]
        dinit_ref[0, n] = h[n]
        da_ref[0, n] = da[n]

    @pl.when(j == grid[2] - 1)
    def _shared():
        db_ref[0] = jnp.sum(pb_scr[...], axis=2)
        dc_ref[0] = jnp.sum(pc_scr[...], axis=2)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


def _specs(N: int, sub: int, nc: int, chunk_of):
    """BlockSpecs by role, on a grid (batch, chunk step, channel block);
    `chunk_of(step)` is the chunk a step works."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = CHUNK
    return dict(
        act=pl.BlockSpec((1, T, sub, _LANES),
                         lambda i, s, j: (i, chunk_of(s), j, 0)),
        a=pl.BlockSpec((N, sub, _LANES), lambda i, s, j: (0, j, 0)),
        bc=pl.BlockSpec((T, N), lambda i, s, j: (i * nc + chunk_of(s), 0),
                        memory_space=pltpu.SMEM),
        state=pl.BlockSpec((1, N, sub, _LANES),
                           lambda i, s, j: (i, 0, j, 0)),
        states=pl.BlockSpec((1, 1, N, sub, _LANES),
                            lambda i, s, j: (i, chunk_of(s), 0, j, 0)),
        shared=pl.BlockSpec((1, T, N, _LANES),
                            lambda i, s, j: (i, chunk_of(s), 0, 0)))


# Jitted for the reason ops/attention.py's calls are: a model's layers
# trace and lower each kernel once a step, not once a layer.
@functools.partial(jax.jit, static_argnames=("sub",))
def _scan_forward_call(x, dt, a, B, C, init, *, sub: int):
    """x, dt [b, S, rows, 128] f32; a [N, rows, 128]; B, C [b * S, N] f32;
    init [b, N, rows, 128] -> (m like x, states [b, chunks, N, rows, 128]:
    the state leaving each chunk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, S, rows, _ = x.shape
    N, nc = a.shape[0], S // CHUNK
    s = _specs(N, sub, nc, lambda step: step)
    call = pl.pallas_call(
        functools.partial(_selective_fwd_kernel, N=N),
        grid=(b, nc, rows // sub),
        in_specs=[s["act"], s["act"], s["a"], s["bc"], s["bc"], s["state"]],
        out_specs=[s["act"], s["states"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, N, rows, _LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows // sub, N, sub, _LANES),
                                   jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("selective_scan_fwd"):
        return call(x, dt, a, B, C, init)


@functools.partial(jax.jit, static_argnames=("sub",))
def _scan_backward_call(x, dt, a, B, C, dm, states, init, dfinal, *,
                        sub: int):
    """-> (dx, d dt like x; dA [b, N, rows, 128]; dB, dC [b, S, N, 128]
    with the lanes still to sum; d init)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, S, rows, _ = x.shape
    N, nc = a.shape[0], S // CHUNK
    grid = (b, nc, rows // sub)
    s = _specs(N, sub, nc, lambda step: nc - 1 - step)
    # The state entering a chunk is the one the chunk before it left.
    entering = pl.BlockSpec(
        (1, 1, N, sub, _LANES),
        lambda i, step, j: (i, jnp.maximum(nc - 2 - step, 0), 0, j, 0))
    tile = (N, sub, _LANES)
    call = pl.pallas_call(
        functools.partial(_selective_bwd_kernel, N=N, grid=grid),
        grid=grid,
        in_specs=[s["act"], s["act"], s["a"], s["bc"], s["bc"], s["act"],
                  entering, s["state"], s["state"]],
        out_specs=[s["act"], s["act"], s["state"], s["shared"], s["shared"],
                   s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(init.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, S, N, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, S, N, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct(init.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((CHUNK + 1, *tile), jnp.float32),
                        pltpu.VMEM((CHUNK, *tile), jnp.float32),
                        pltpu.VMEM((CHUNK, *tile), jnp.float32),
                        pltpu.VMEM((rows // sub, *tile), jnp.float32),
                        pltpu.VMEM((rows // sub, *tile), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret(),
    )
    with jax.named_scope("selective_scan_bwd"):
        return call(x, dt, a, B, C, dm, states, init, dfinal)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def selective_scan(x, dt, A, B, C, D, initial_state=None):
    """The selective scan of Mamba-1 over a whole sequence.

    x [b, S, channels]; dt [b, S, channels] the step sizes, positive
    (after the softplus); A [channels, N] negative; B, C [b, S, N]; D
    [channels]; `initial_state` [b, channels, N] or None for zeros.
    Returns (m [b, S, channels] in x's dtype, the final state
    [b, channels, N] float32). Differentiable in everything: the kernels
    on a TPU, `selective_scan_reference` elsewhere."""
    b, _, channels = x.shape
    f32 = jnp.float32
    if initial_state is None:
        initial_state = jnp.zeros((b, channels, A.shape[1]), f32)
    m, final = _scan(x, dt.astype(f32), A.astype(f32), B, C,
                     initial_state.astype(f32))
    skip = D.astype(f32) * x.astype(f32)
    return (m.astype(f32) + skip).astype(x.dtype), final


@jax.custom_vjp
def _scan(x, dt, A, B, C, init):
    return _scan_fwd(x, dt, A, B, C, init)[0]


def _zero_skip(x):
    return jnp.zeros((x.shape[-1],), jnp.float32)


def _tiled(t, padded: int):
    """[b, S, channels] -> float32 [b, padded, rows, 128], zeros behind."""
    b, S, channels = t.shape
    t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, padded - S), (0, 0)))
    return t.reshape(b, padded, channels // _LANES, _LANES)


def _rows_of(t, padded: int):
    """B or C [b, S, N] -> float32 [b * padded, N]: the kernels' scalars."""
    b, S, N = t.shape
    t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, padded - S), (0, 0)))
    return t.reshape(b * padded, N)


def _state_tiled(s):
    """[b, channels, N] -> [b, N, rows, 128]."""
    b, channels, N = s.shape
    return s.swapaxes(1, 2).reshape(b, N, channels // _LANES, _LANES)


def _state_untiled(s):
    b, N, rows, lanes = s.shape
    return s.reshape(b, N, rows * lanes).swapaxes(1, 2)


@jax.named_scope("selective_scan_fwd")
def _scan_fwd(x, dt, A, B, C, init):
    b, S, channels = x.shape
    if not _kernel_ok(channels):
        out = selective_scan_reference(x, dt, A, B, C, _zero_skip(x), init)
        return out, (x, dt, A, B, C, init, None)
    plan = selective_scan_plan(S, channels, A.shape[1])
    L = plan.padded_len
    m, states = _scan_forward_call(
        _tiled(x, L), _tiled(dt, L), _state_tiled(A[None])[0],
        _rows_of(B, L), _rows_of(C, L), _state_tiled(init),
        sub=plan.block[0])
    # What the forward kernel made and a backward pass reads, by name: the
    # states are the backward kernel's, m the gate's and the output
    # projection's after it (and every GMU layer's). A rematerialised
    # block keeps both and the forward kernel runs once
    # (models/decoder.py KEPT_UNDER_REMAT).
    m = checkpoint_name(m.reshape(b, L, channels)[:, :S].astype(x.dtype),
                        "selective_scan_m")
    states = checkpoint_name(states, "selective_scan_states")
    return (m, _state_untiled(states[:, -1])), (x, dt, A, B, C, init, states)


@jax.named_scope("selective_scan_bwd")
def _scan_bwd(residuals, cotangents):
    x, dt, A, B, C, init, states = residuals
    dm, dfinal = cotangents
    if states is None:
        _, vjp = jax.vjp(
            lambda *args: selective_scan_reference(
                *args[:5], _zero_skip(x), args[5]), x, dt, A, B, C, init)
        return vjp((dm, dfinal))
    b, S, channels = x.shape
    plan = selective_scan_plan(S, channels, A.shape[1])
    L = plan.padded_len
    dx, ddt, dA, dB, dC, dinit = _scan_backward_call(
        _tiled(x, L), _tiled(dt, L), _state_tiled(A[None])[0],
        _rows_of(B, L), _rows_of(C, L), _tiled(dm, L), states,
        _state_tiled(init), _state_tiled(dfinal.astype(jnp.float32)),
        sub=plan.block[0])

    def untiled(t):
        return t.reshape(b, L, channels)[:, :S]
    return (untiled(dx).astype(x.dtype), untiled(ddt),
            jnp.sum(_state_untiled(dA), axis=0),
            jnp.sum(dB, axis=-1)[:, :S].astype(B.dtype),
            jnp.sum(dC, axis=-1)[:, :S].astype(C.dtype),
            _state_untiled(dinit))


_scan.defvjp(_scan_fwd, _scan_bwd)
