"""Elementwise / normalization layers used by the model stack.

These stay as plain jax ops on purpose: XLA fuses them into surrounding
matmuls (HBM-bandwidth guidance — don't hand-schedule what the compiler
already fuses); Pallas is reserved for ops XLA can't fuse (attention).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


def rope(x, position_offset=0, base: float = ROPE_BASE, positions=None,
         inv_freq=None):
    """Rotary position embedding for [batch, heads, seq, head_dim].

    `positions` overrides `position_offset` and may be traced: shape
    (seq,) — the KV-cache decode path passes start_pos + arange — or
    (batch, seq) for per-sequence offsets (continuous batching decodes
    every slot at its own position). `inv_freq` [head_dim / 2], where
    given, are the pairs' frequencies themselves (a scaled context's, such
    as `yarn_inv_freq`'s) and `base` is not read. One implementation
    serves train and decode so the formulas can't diverge."""
    *_, seq_len, head_dim = x.shape
    if positions is None:
        positions = position_offset + jnp.arange(seq_len)
    pos = jnp.asarray(positions, jnp.float32)
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
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


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's blended frequencies of `dim` rotated columns (arXiv:2309.00071,
    as DeepSeek-V3's rotary has them), a tuple of dim / 2 floats for
    `rope`'s `inv_freq`: pair i turns at f_i = base^(-2i / dim) where it
    makes more than `beta_fast` turns over the `original` context, at
    f_i / factor where it makes fewer than `beta_slow`, and at a linear
    blend of the two between: with c(r) = dim ln(original / (2 pi r)) /
    (2 ln base), low = floor(c(beta_fast)), high = ceil(c(beta_slow)), both
    clipped to [0, dim - 1], ramp_i = clip((i - low) / (high - low), 0, 1)
    and inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i). Plain Python
    floats: a config can hold them and a trace reads them as constants."""
    def pair_of(turns: float) -> float:
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    span = max(high - low, 1e-3)        # the published guard of low == high
    out = []
    for i in range(dim // 2):
        f = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / span, 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return tuple(out)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature m(a) = 0.1 a ln(factor) + 1 (1 at a
    factor of 1 or less): a softmax scale carries m(mscale_all_dim)^2, cos
    and sin m(mscale) / m(mscale_all_dim)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    # The two names are for a rematerialised block that has room for them
    # (models/decoder.py KEPT_WHERE_IT_FITS).
    gate = checkpoint_name(jnp.einsum("...d,df->...f", x, w_gate),
                           "mlp_gate_up")
    up = checkpoint_name(jnp.einsum("...d,df->...f", x, w_up), "mlp_gate_up")
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, w_down)


def _windows(x, tail, K):
    """The K shifted views of (tail | x) a tap reads, in float32: view k
    holds row t + k of (tail | x) at row t."""
    padded = jnp.concatenate([tail, x], axis=1)
    return [padded[:, k:k + x.shape[1]].astype(jnp.float32)
            for k in range(K)]


def _taps(windows, weight, bias, dtype):
    """bias + sum_k weight[:, k] * windows[k]: K multiply-adds in float32,
    the bias first (None: there is none, and nothing is added for it),
    rounded to `dtype`."""
    w = weight.astype(jnp.float32)
    y = None if bias is None else bias.astype(jnp.float32)
    for k, view in enumerate(windows):
        y = view * w[:, k] if y is None else y + view * w[:, k]
    return y.astype(dtype)


@jax.custom_vjp
def _conv_silu(x, weight, bias, tail):
    return jax.nn.silu(_taps(_windows(x, tail, weight.shape[1]), weight,
                             bias, x.dtype))


def _conv_silu_fwd(x, weight, bias, tail):
    # What the function was handed and nothing made inside: no name joins
    # a block's KEPT_UNDER_REMAT for it. The backward pass makes the
    # pre-activation again from x (where a rematerialised block has just
    # made it, XLA reads that one instead: the compiler's choice, and the
    # faster one on the chip).
    return _conv_silu(x, weight, bias, tail), (x, weight, bias, tail)


def _conv_silu_bwd(residuals, dy):
    x, weight, bias, tail = residuals
    K, (b, L, C) = weight.shape[1], x.shape
    f32 = jnp.float32
    windows = _windows(x, tail, K)
    pre = _taps(windows, weight, bias, x.dtype).astype(f32)
    sig = jax.nn.sigmoid(pre)
    g = dy.astype(f32) * (sig * (1.0 + pre * (1.0 - sig)))
    # The taps' and the bias's gradients: float32 column sums of the pass
    # that makes g, over the same shifted rows the forward read.
    dw = jnp.stack([jnp.sum(g * view, axis=(0, 1)) for view in windows],
                   axis=1)
    db = None if bias is None else jnp.sum(g, axis=(0, 1)).astype(bias.dtype)
    # d(tail | x)[j] = sum_k weight[:, k] * g[j - k]: the same K shifted
    # multiply-adds run the other way, over g (rounded once, as x is)
    # between K - 1 rows of zeros on either side.
    zeros = jnp.zeros((b, K - 1, C), x.dtype)
    gz = jnp.concatenate([zeros, g.astype(x.dtype), zeros], axis=1)
    w = weight.astype(f32)

    def rows(first, n):
        return sum(gz[:, first + K - 1 - k:first + K - 1 - k + n].astype(f32)
                   * w[:, k] for k in range(K))

    return (rows(K - 1, L).astype(x.dtype), dw.astype(weight.dtype), db,
            rows(0, K - 1).astype(tail.dtype))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv1d_silu(x, weight, bias, tail=None):
    """silu of a depthwise causal convolution along the sequence: x
    [b, L, C], weight [C, K] (K taps, the last one on the current
    position), bias [C] or None for a convolution that has none: y_t =
    silu(bias + sum_k weight[:, k] x_{t-K+1+k}),
    with `tail` [b, K-1, C] the inputs before x (zeros where None). K
    shifted multiply-adds in float32, no kernel. Returns (y like x, the
    last K-1 inputs: the tail a cache hands to the next call).

    The backward pass is written by hand (one `jax.custom_vjp`), not
    derived. Autodiff transposes each of the K shifted slices into a
    `pad`, and XLA:TPU fuses none of them with what feeds it: at
    granite-4.0-h-micro's [1, 16384, 4352] the compiled step wrote the
    cotangent times each tap, each product rounded to bfloat16, as four
    arrays, 570 MB a layer, and read them back through four pads to add
    them: four passes, 3.85 ms a layer. Here there are two (2.54 ms):
    g = dy * silu'(pre) in float32 from x and dy, with the taps' and the
    bias's gradients as float32 column sums of that same pass; then dx,
    the same K shifted multiply-adds run the other way over g (an
    anti-causal convolution), added in float32 and rounded once. A pass
    over K shifted slices takes 1.1 ms there whatever its bytes (the
    vector unit's shifts, not HBM, which x, dy and dx cross in 0.5), so
    fewer passes is all there is to take without a kernel. The residuals
    are x, the taps, the bias and the tail: nothing made inside is kept."""
    K = weight.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    tail = tail.astype(x.dtype)
    # The new tail is plain slicing outside the rule: autodiff's, and
    # nothing at all in a train step, which hands no tail on.
    return (_conv_silu(x, weight, bias, tail),
            jnp.concatenate([tail, x], axis=1)[:, x.shape[1]:])


def _head_indicator(width: int, heads: int):
    """[width, heads] float32, 1 where a column is one of a head's."""
    return (jnp.arange(width)[:, None] // (width // heads)
            == jnp.arange(heads)[None, :]).astype(jnp.float32)


def head_sums(x, heads: int):
    """x [..., heads * W] float32 -> [..., heads]: the sum over each
    head's W columns, as a product with a 0/1 matrix. The plain form, a
    reshape to [..., heads, W] and a sum, makes XLA:TPU lay the array out
    again whatever W is: the heads axis becomes the second-minor one, so
    the rows that lay on the sublanes of an (8, 128) tile give way to the
    heads, and every element moves. It is not a matter of W being off the
    128 lanes: at 96 and 192 the two norms of a delta-rule layer took 16
    and 30 ms a layer and step at 16,384 tokens that way (PERF.md section
    6, PR 41), and at 512, four whole tiles, Mamba-2's gated norm over
    eight groups took 10.8 ms a layer and step where this form takes 5.6
    (PR 47). This one stays in the projections' own layout."""
    return jnp.einsum("...e,eh->...h", x, _head_indicator(x.shape[-1], heads),
                      precision=jax.lax.Precision.HIGHEST)


def head_spread(s, width: int):
    """s [..., heads] float32 -> [..., width]: each head's value on all of
    its columns, `head_sums` the other way round (exact: one term a
    column)."""
    return jnp.einsum("...h,eh->...e", s, _head_indicator(width, s.shape[-1]),
                      precision=jax.lax.Precision.HIGHEST)


def head_rms_norm(t, weight, eps: float = NORM_EPS):
    """An RMSNorm over each head's own columns of t [..., heads * W], one
    weight [W] for all heads (as many heads as the weight goes into the
    width), in t's own layout: the mean of squares a head and its root's
    way back onto the columns are `head_sums` and `head_spread`. In
    float32, -> t's dtype."""
    width, W = t.shape[-1], weight.shape[0]
    tf = t.astype(jnp.float32)
    inv = jax.lax.rsqrt(head_sums(jnp.square(tf), width // W) / W + eps)
    return (tf * head_spread(inv, width) * jnp.tile(
        weight.astype(jnp.float32), width // W)).astype(t.dtype)


def head_rms_norm_gated(o, gate, weight, eps: float = NORM_EPS):
    """Gated DeltaNet's output norm, the other order from `gated_rms_norm`
    below: an RMSNorm over each head's own columns FIRST (o [..., H, V],
    one weight [V] for all heads), THEN the gate, rmsnorm_h(o; weight) *
    silu(gate), gate [..., H * V]. -> [..., H * V] in o's dtype."""
    H, V = o.shape[-2:]
    of = o.reshape(gate.shape).astype(jnp.float32)
    inv = jax.lax.rsqrt(head_sums(jnp.square(of), H) / V + eps)
    normed = of * head_spread(inv, H * V) * jnp.tile(
        weight.astype(jnp.float32), H)
    return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(o.dtype)


def gated_rms_norm(y, gate, weight, eps: float = NORM_EPS, groups: int = 1):
    """Mamba-2's output norm: the gate first, then an RMSNorm over each of
    `groups` equal runs of channels (one group: ONE norm over all of
    them), rmsnorm_g(y * silu(gate)) * weight, one weight a channel.
    Several groups never get an axis of their own (`head_sums` tells
    why): a group's mean of squares and its root's way back onto the
    channels are the two 0/1 products, in y's own layout."""
    gated = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    if groups == 1:
        return rms_norm(gated, weight, eps).astype(y.dtype)
    width = gated.shape[-1]
    inv = jax.lax.rsqrt(head_sums(jnp.square(gated), groups)
                        / (width // groups) + eps)
    return (gated * head_spread(inv, width)
            * weight.astype(jnp.float32)).astype(y.dtype)
