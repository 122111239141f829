"""Elementwise / normalization layers used by the model stack.

These stay as plain jax ops on purpose: XLA fuses them into surrounding
matmuls (HBM-bandwidth guidance — don't hand-schedule what the compiler
already fuses); Pallas is reserved for ops XLA can't fuse (attention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What a model that names neither constant runs on (models.decoder.Decoder).
NORM_EPS = 1e-6
ROPE_BASE = 10000.0


def rms_norm(x, weight, eps: float = NORM_EPS):
    """RMSNorm; computed in fp32, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm with weight and bias; computed in fp32, cast back to the
    input dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    centred = xf - mean
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rope(x, position_offset=0, base: float = ROPE_BASE, positions=None):
    """Rotary position embedding for [batch, heads, seq, head_dim].

    `positions` overrides `position_offset` and may be traced: shape
    (seq,) — the KV-cache decode path passes start_pos + arange — or
    (batch, seq) for per-sequence offsets (continuous batching decodes
    every slot at its own position). One implementation serves train
    and decode so the formulas can't diverge."""
    *_, seq_len, head_dim = x.shape
    if positions is None:
        positions = position_offset + jnp.arange(seq_len)
    pos = jnp.asarray(positions, jnp.float32)
    inv_freq = 1.0 / (base ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if pos.ndim == 2:                                # (batch, seq)
        angles = pos[:, :, None] * inv_freq          # (b, seq, d/2)
        cos = jnp.cos(angles)[:, None]               # (b, 1, seq, d/2)
        sin = jnp.sin(angles)[:, None]
    else:
        angles = pos[:, None] * inv_freq[None, :]    # (seq, d/2)
        cos = jnp.cos(angles)[None, None]
        sin = jnp.sin(angles)[None, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    gate = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    up = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", gate * up, w_down)


def causal_conv1d(x, weight, bias, tail=None):
    """Depthwise causal convolution along the sequence: x [b, L, C],
    weight [C, K] (K taps, the last one on the current position), bias
    [C]: y_t = bias + sum_k weight[:, k] x_{t-K+1+k}, with `tail`
    [b, K-1, C] the inputs before x (zeros where None). K shifted
    multiply-adds in float32, no kernel. Returns (y like x, the last K-1
    inputs: the tail a cache hands to the next call)."""
    K = weight.shape[1]
    L = x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(K):
        y = y + padded[:, k:k + L].astype(jnp.float32) * w[:, k]
    return y.astype(x.dtype), padded[:, L:]


def gated_rms_norm(y, gate, weight, eps: float = NORM_EPS):
    """Mamba-2's output norm with one group: the gate first, then ONE
    RMSNorm over all channels, rmsnorm(y * silu(gate); weight)."""
    gated = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return rms_norm(gated, weight, eps).astype(y.dtype)
