"""Causal multi-head attention: Pallas TPU flash kernels + jax reference.

Net-new vs the reference codebase (SURVEY.md §2.4: no attention kernels
in-tree — torch users bring their own): blockwise online-softmax (flash)
attention written for the TPU memory hierarchy, forward AND backward:

* Forward: Q tiles stream through VMEM; K/V are tiled over the innermost
  grid dimension (never whole-sequence VMEM-resident, so sequence length
  is bounded by HBM, not VMEM); fp32 accumulators persist in VMEM scratch
  across the K sweep; the log-sum-exp per row is saved for the backward.
* Backward: flash-2 style blockwise dQ (Q-outer, K-inner sweep) and
  dK/dV (K-outer, Q-inner sweep) kernels that recompute attention
  probabilities per block from the saved logsumexp — no (seq, seq)
  matrix is ever materialized, so long-context *training* fits.

Layout: [batch, heads, seq, head_dim]. The jax reference implementation
serves non-TPU backends, sequences that are not a multiple of 128, and
correctness tests; set RAY_TPU_PALLAS_INTERPRET=1 to run the kernels in
interpreter mode on CPU (the SURVEY §4 CPU-mirror pattern for kernel
tests). On a TPU backend the kernels are the only path for a tileable
sequence: a kernel that fails to compile is an error.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference implementation (CPU tests, non-TPU backends)
# ---------------------------------------------------------------------------
def mha_reference(q, k, v, causal: bool = True,
                  sm_scale: Optional[float] = None):
    """Plain XLA attention; numerically the ground truth for the kernel."""
    *_, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(head_dim)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(
            jnp.ones((seq_q, seq_k), dtype=bool), k=seq_k - seq_q)
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _interpret() -> bool:
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


def _on_tpu() -> bool:
    """Whether the kernels run: compiled on a TPU backend, interpreted
    where RAY_TPU_PALLAS_INTERPRET=1 asks for it on another backend. A
    backend that fails to initialise raises here, and interpret mode on
    a TPU is refused: neither may quietly pick an implementation."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if _interpret():
        if on_tpu:
            raise RuntimeError(
                "RAY_TPU_PALLAS_INTERPRET=1 on a TPU backend: the "
                "interpreter is the CPU test mode; unset it to run the "
                "compiled kernels")
        return True
    return on_tpu


def _kernel_ok(seq_len: int) -> bool:
    return _on_tpu() and seq_len >= 128 and seq_len % 128 == 0


# (mesh, PartitionSpec over [batch, heads, seq, head_dim]) while a sharded
# step is being traced; see kernel_sharding.
_SHARDING: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_sharding", default=None)


@contextlib.contextmanager
def kernel_sharding(mesh, spec):
    """Trace the enclosed step with the parts XLA cannot partition run
    once per shard of `mesh`: q/k/v [batch, heads, seq, head_dim] are
    split by `spec`, which may name mesh axes for batch and heads only —
    each kernel instance sees whole sequences — and ops.loss scans each
    chip's own rows of the batch axes.

    A pallas_call is opaque to the SPMD partitioner: jax refuses to lower
    one inside a program partitioned over several devices ("Mosaic
    kernels cannot be automatically partitioned"); a scan that slices
    the batch at a traced offset is gathered whole onto every chip. So a
    sharded train step states here how its batch and heads are split
    (models._training.make_train_step_for does, from its rule table)."""
    if spec[2:] != (None,) * len(spec[2:]):
        raise ValueError(
            f"flash_attention kernels need whole sequences and head_dim; "
            f"{spec} splits them (parallel.sequence does sequence "
            f"parallelism)")
    token = _SHARDING.set((mesh, spec))
    try:
        yield
    finally:
        _SHARDING.reset(token)


def step_sharding():
    """(mesh, spec) of the enclosing kernel_sharding, or None."""
    return _SHARDING.get()


def _per_shard(fn):
    """`fn` over q-shaped arrays, run per shard under kernel_sharding."""
    ctx = step_sharding()
    if ctx is None:
        return fn
    mesh, spec = ctx
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def _pick_block(seq_len: int) -> int:
    """Largest block that divides the sequence: fewer grid steps amortize
    the per-step VPU/online-softmax overhead (measured in round 3 on a
    v5e, not re-measured: 512 beats 128 by ~2.5x at S=2048, and 1024
    beats 512 by ~10% at S=1024). Capped at 1024: the f32 score block
    is block_q*block_k*4B of VMEM (4 MB at 1024²); the causal index
    clamp assumes exact tiling."""
    for b in (1024, 512, 256, 128):
        if seq_len % b == 0:
            return b
    return seq_len


# ---------------------------------------------------------------------------
# Forward kernel: grid (bh, q_blocks, k_blocks); K innermost so fp32
# accumulators ride VMEM scratch across the K sweep.
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                causal: bool, block_q: int, block_k: int,
                save_lse: bool):
    if save_lse:
        lse_ref, acc_scr, m_scr, l_scr = rest
    else:
        lse_ref = None
        acc_scr, m_scr, l_scr = rest
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    # Causal: K blocks strictly right of the Q block's last row contribute
    # nothing; skip their compute entirely (the grid still steps, the
    # body is predicated off).
    needed = (ki * block_k <= qi * block_q + block_q - 1) if causal \
        else (ki >= 0)

    @pl.when(needed)
    def _compute():
        # Dots run on the operands' native dtype (bf16 hits the MXU at
        # full rate; pre-casting to f32 would quarter it) and accumulate
        # in f32 via preferred_element_type.
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        # Fully-masked rows (can't happen causally, but keep it safe for
        # degenerate inputs): avoid 0/0.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        if save_lse:
            lse = m_scr[...] + jnp.log(l_safe)      # (block_q,)
            lse_ref[0] = jax.lax.broadcast_in_dim(
                lse, (block_q, 128), (0,))


def _flash_forward(q, k, v, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, save_lse: bool = True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_len, head_dim = q.shape
    bh = batch * heads
    qf = q.reshape(bh, seq_len, head_dim)
    kf = k.reshape(bh, seq_len, head_dim)
    vf = v.reshape(bh, seq_len, head_dim)

    block_q = min(block_q, seq_len)
    block_k = min(block_k, seq_len)
    grid = (bh, pl.cdiv(seq_len, block_q), pl.cdiv(seq_len, block_k))

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, save_lse=save_lse)
    if causal:
        # Upper-triangle K blocks are never used: clamp their index to
        # the diagonal so Mosaic sees an unchanged block and skips the
        # HBM->VMEM DMA entirely (the compute is pl.when-predicated off).
        ratio = max(1, block_q // block_k)
        def kv_index(b, i, j):
            return (b, jnp.minimum(j, (i + 1) * ratio - 1)
                    if ratio > 1 else jnp.minimum(j, i), 0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)
    out_specs = [
        pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct(qf.shape, q.dtype)]
    if save_lse:
        # lse is lane-replicated to 128 so its block satisfies the TPU
        # (8, 128) tile rule (the layout jax's own TPU flash kernel uses
        # for its residuals). Inference-only forwards skip it entirely —
        # pallas outputs are opaque to XLA DCE, so an unused lse would
        # still cost seq*128*4 bytes of HBM writes per (batch, head).
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, seq_len, 128), jnp.float32))
    fwd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), kv_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    # A scope, never pallas_call(name=...): the scope reaches the name of
    # the HLO instruction, which is what a device trace shows, and leaves
    # kernel_name (_fwd_kernel) as it is (util/profiling.py DEVICE_SCOPES).
    with jax.named_scope("flash_attention_fwd"):
        result = fwd(qf, kf, vf)
    out = result[0].reshape(batch, heads, seq_len, head_dim)
    # lse stays lane-replicated (.., seq, 128): the backward feeds it
    # straight back to the kernels, avoiding a slice + rebroadcast HBM
    # round trip per training step. It carries q's leading [batch, heads]
    # so it shards like q (see kernel_sharding).
    lse = result[1].reshape(batch, heads, seq_len, 128) if save_lse \
        else None
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (flash-2): recompute P per block from saved lse.
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale: float, causal: bool,
               block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    needed = (ki * block_k <= qi * block_q + block_q - 1) if causal \
        else (ki >= 0)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[:, None])                     # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                causal: bool, block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Causal: Q blocks whose last row is above the K block's first row
    # see none of it.
    needed = (qi * block_q + block_q - 1 >= ki * block_k) if causal \
        else (qi >= 0)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        # s_T: (bk, bq)
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, DEFAULT_MASK_VALUE)
        p_t = jnp.exp(s_t - lse[None, :])                 # (bk, bq)
        dv_scr[...] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, bq)
        ds_t = p_t * (dp_t - delta[None, :]) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                    block_q: int, block_k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_len, head_dim = q.shape
    bh = batch * heads
    block_q = min(block_q, seq_len)
    block_k = min(block_k, seq_len)
    qf = q.reshape(bh, seq_len, head_dim)
    kf = k.reshape(bh, seq_len, head_dim)
    vf = v.reshape(bh, seq_len, head_dim)
    dof = g.reshape(bh, seq_len, head_dim)
    lsef = lse.reshape(bh, seq_len, 128)  # lane-replicated by forward
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce in XLA.
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1).reshape(bh, seq_len)[:, :, None],
        (bh, seq_len, 128))

    # Causal index clamps: blocks that the pl.when predicate skips are
    # mapped to the previously-fetched block so Mosaic elides their DMA.
    kq_ratio = max(1, block_q // block_k)
    qk_ratio = max(1, block_k // block_q)
    if causal:
        def dq_kv_index(b, i, j):
            return (b, jnp.minimum(j, (i + 1) * kq_ratio - 1), 0)

        def dkv_q_index(b, i, j):
            return (b, jnp.maximum(j, i * qk_ratio), 0)
    else:
        def dq_kv_index(b, i, j):
            return (b, j, 0)

        def dkv_q_index(b, i, j):
            return (b, j, 0)
    q_spec = pl.BlockSpec((1, block_q, head_dim),
                          lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kq_spec = pl.BlockSpec((1, block_k, head_dim), dq_kv_index,
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)


    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, pl.cdiv(seq_len, block_q), pl.cdiv(seq_len, block_k)),
        in_specs=[q_spec, kq_spec, kq_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_attention_dq"):
        dq = dq_call(qf, kf, vf, dof, lsef, delta)

    # dK/dV: K-outer, Q-inner sweep.
    k_spec = pl.BlockSpec((1, block_k, head_dim),
                          lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    qk_spec = pl.BlockSpec((1, block_q, head_dim), dkv_q_index,
                           memory_space=pltpu.VMEM)

    def dkv_row_index(b, i, j):
        bi, ji, _ = dkv_q_index(b, i, j)
        return (bi, ji, 0)
    row_j_spec = pl.BlockSpec((1, block_q, 128), dkv_row_index,
                              memory_space=pltpu.VMEM)
    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, pl.cdiv(seq_len, block_k), pl.cdiv(seq_len, block_q)),
        in_specs=[qk_spec, k_spec, k_spec, qk_spec, row_j_spec,
                  row_j_spec],  # full-row lse/delta; sliced by q block
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_attention_dkv"):
        dk, dv = dkv_call(qf, kf, vf, dof, lsef, delta)

    shape = (batch, heads, seq_len, head_dim)
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None):
    """Flash attention: Pallas kernels on TPU, reference elsewhere.

    Differentiable end to end without materializing the (seq, seq)
    probability matrix: the backward recomputes attention blockwise from
    the saved logsumexp (flash-2), so both inference AND training scale
    to long sequences (SURVEY.md hard-part #5).
    """
    # Primal-only call (no differentiation): skip the lse residual.
    out, _ = _flash_attention_fwd_impl(q, k, v, causal, sm_scale,
                                       save_lse=False)
    return out


def _scale_of(q, sm_scale):
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(
        q.shape[-1])


def _flash_attention_fwd_impl(q, k, v, causal, sm_scale,
                              save_lse=True):
    scale = _scale_of(q, sm_scale)
    seq_len = q.shape[-2]
    if _kernel_ok(seq_len):
        block = _pick_block(seq_len)
        out, lse = _per_shard(functools.partial(
            _flash_forward, causal=causal, sm_scale=scale,
            block_q=block, block_k=block, save_lse=save_lse))(q, k, v)
        return out, (out, lse)
    return mha_reference(q, k, v, causal, scale), (None, None)


def _flash_fwd(q, k, v, causal, sm_scale):
    out, (o_saved, lse) = _flash_attention_fwd_impl(
        q, k, v, causal, sm_scale)
    return out, (q, k, v, o_saved, lse)


def _flash_bwd(causal, sm_scale, residuals, g):
    q, k, v, o, lse = residuals
    scale = _scale_of(q, sm_scale)
    if o is None:
        # Non-kernel path: autodiff through the reference.
        _, vjp = jax.vjp(
            lambda q_, k_, v_: mha_reference(q_, k_, v_, causal, sm_scale),
            q, k, v)
        return vjp(g)
    block = _pick_block(q.shape[-2])
    return _per_shard(functools.partial(
        _flash_backward, causal=causal, sm_scale=scale,
        block_q=block, block_k=block))(q, k, v, o, lse, g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
