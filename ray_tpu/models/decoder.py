"""The decoder's compute, written once: what models.gpt, models.llama and
models.moe train and what models.generate prefills and decodes.

A family says what it is with a `Decoder`, which its config's `decoder()`
builds from the fields it already has: the attention's head counts, its
channel mixer, its remat policy, the rope base (None: no positions at
all), the norm eps, the score scale and the sizes of a state-space mixer
where it names them (ops.layers' own, 1/sqrt(head_dim), none where it
does not), and what its embedding, its two residual branches and its
logits are multiplied by (1 where it says nothing). Nothing here reads a
config: one family differs from another by those values and by which
weights a layer holds (`in_proj`: a Mamba-2 layer; else attention, from
`wqkv` or `wq` + `wkv`, with `q_norm` or not), and by nothing else. A
model's layers need not be alike: each picks its mixer by what it holds.

    decoder_hidden      embedding, layer stack, final norm, head
      attention | mamba2   the sequence mixers, (x, layer, dec, cache,
                        start_pos) -> (y, new cache): attention is the
                        flash kernel over the whole sequence with no
                        cache, with one a write into it and a masked read
                        of it; mamba2 the chunked scan (ops.ssm_scan) over
                        the tokens given, from the cached state where
                        there is one, and one step of the recurrence for a
                        single token
      gelu_mlp | swiglu_mlp | routed_experts   the channel mixers,
                        (y, layer) -> (out, stats or None)

Cache layout, per layer by its kind: attention {"k"|"v": [batch,
n_kv_heads, max_len, head_dim]}; Mamba-2 {"conv": [batch, d_conv - 1,
inner + 2 groups x state] the convolution's last inputs, "ssm": [batch,
heads, head_dim, state] float32}, which does not grow.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import DEFAULT_MASK_VALUE, flash_attention
from ..ops.layers import (NORM_EPS, ROPE_BASE, causal_conv1d, gated_rms_norm,
                          rms_norm, rope, swiglu)
from ..ops.ssm_scan import ssm_scan
from ..parallel.moe import dropless_moe_layer


class Decoder(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    mlp: Callable                   # (y, layer) -> (out, stats or None)
    remat: Optional[Callable]       # a jax.checkpoint policy; None: keep all
    rope_base: Optional[float] = ROPE_BASE     # None: no rotary
    norm_eps: float = NORM_EPS
    sm_scale: Optional[float] = None           # None: 1 / sqrt(head_dim)
    residual_scale: float = 1.0                # on both branches of a block
    embed_scale: float = 1.0
    logit_scale: float = 1.0                   # on the final-norm rows
    # A Mamba-2 layer's sizes (layers that hold `in_proj`): inner width
    # ssm_heads * ssm_head_dim; B and C are ssm_groups * ssm_state wide.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256


def gelu_mlp(y, layer):
    # gelu is fused into the matmuls by XLA
    hidden = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, layer["w1"]))
    return jnp.einsum("bsf,fd->bsd", hidden, layer["w2"]), None


def swiglu_mlp(y, layer):
    return swiglu(y, layer["w_gate"], layer["w_up"], layer["w_down"]), None


def routed_experts(y, layer, experts_per_token: int, norm_topk_prob: bool):
    """Dropless top-k SwiGLU experts over the flattened tokens; `stats`
    are parallel.moe.dropless_moe_layer's, summable over layers."""
    b, s, d = y.shape
    out, stats = dropless_moe_layer(
        y.reshape(b * s, d), layer["router"], layer["expert_gate"],
        layer["expert_up"], layer["expert_down"],
        experts_per_token=experts_per_token,
        norm_topk_prob=norm_topk_prob)
    return out.reshape(b, s, d), stats


def _is_mamba2(layer) -> bool:
    return "in_proj" in layer


def empty_cache(dec: Decoder, layers, batch, max_len, dtype) -> List[Dict]:
    """The state of each of `layers` (a model's `params["layers"]`, or
    anything that holds their keys), by its kind: an attention layer its
    kv heads up to `max_len`, not their copies across a group; a Mamba-2
    layer its convolution's last inputs and its state."""
    def one(layer):
        if _is_mamba2(layer):
            conv_dim = (dec.ssm_heads * dec.ssm_head_dim
                        + 2 * dec.ssm_groups * dec.ssm_state)
            taps = layer["conv_w"].shape[1]
            return {"conv": jnp.zeros((batch, taps - 1, conv_dim), dtype),
                    "ssm": jnp.zeros((batch, dec.ssm_heads, dec.ssm_head_dim,
                                      dec.ssm_state), jnp.float32)}
        shape = (batch, dec.n_kv_heads, max_len, dec.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return [one(layer) for layer in layers]


def _across_group(t, group: int):
    """GQA: each kv head [b, kvh, ., hd] serves its whole query group.
    (GQA inside the kernels, with no expanded copy, is in ROADMAP's list
    of what the system cannot run yet.)"""
    return t if group == 1 else jnp.repeat(t, group, axis=1)


def _cached_attention(q, k, v, cache, sp, group: int, sm_scale):
    """Write k, v [b, kvh, L, hd] into the cache at positions sp + [0, L)
    and attend q [b, h, L, hd] over the cache up to each query's own
    position. Only the write and the mask specialize on whether `sp` is
    a scalar or one position a row."""
    b, _, L, hd = q.shape
    max_len = cache["k"].shape[-2]
    if sp.ndim == 1:
        rows = jnp.arange(b)[:, None]                    # (b, 1)
        cols = sp[:, None] + jnp.arange(L)[None]         # (b, L)
        # Advanced indexing on axes 0 and 2 moves the index dims to
        # the front: value shape (b, L, kvh, hd).
        k_cache = cache["k"].at[rows, :, cols, :].set(
            k.transpose(0, 2, 1, 3).astype(cache["k"].dtype))
        v_cache = cache["v"].at[rows, :, cols, :].set(
            v.transpose(0, 2, 1, 3).astype(cache["v"].dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, sp, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, sp, 0))

    scale = hd ** -0.5 if sm_scale is None else sm_scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   _across_group(k_cache, group).astype(jnp.float32)) * scale
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 1)
    if sp.ndim == 1:
        q_pos = sp[:, None, None] + q_iota[None]         # (b, L, max)
        mask = (k_pos[None] <= q_pos)[:, None]           # (b,1,L,max)
    else:
        mask = (k_pos <= sp + q_iota)[None, None]        # (1,1,L,max)
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    v_all = _across_group(v_cache, group)
    attn = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_all.dtype), v_all)
    return attn, {"k": k_cache, "v": v_cache}


def attention(x, layer, dec: Decoder, cache=None, start_pos=None):
    """Causal self-attention of x [b, L, d], from the input norm to the
    output projection. With a cache, `start_pos` is the absolute offset
    of x's positions — a scalar (all rows aligned: prefill / single-stream
    decode) or a [b] vector (continuous batching: every row decodes at its
    own position). One implementation serves training, prefill and decode
    so the formulas can't diverge. Returns (y, new_cache or None)."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim

    def heads(t, n):
        return t.reshape(b, L, n, hd).transpose(0, 2, 1, 3)

    y = rms_norm(x, layer["ln1"], dec.norm_eps)
    if "wqkv" in layer:
        q, k, v = jnp.split(checkpoint_name(
            jnp.einsum("bsd,de->bse", y, layer["wqkv"]), "attention_qkv"),
            3, axis=-1)
    else:
        q = jnp.einsum("bsd,de->bse", y, layer["wq"])
        k, v = jnp.split(
            jnp.einsum("bsd,de->bse", y, layer["wkv"]), 2, axis=-1)
    if "q_norm" in layer:           # over all of q and of k, before the split
        q = rms_norm(q, layer["q_norm"], dec.norm_eps)
        k = rms_norm(k, layer["k_norm"], dec.norm_eps)
    # Rotary embeddings at absolute (possibly traced) positions, [L] or
    # [b, L]; with no cache rope counts from 0 itself. A model with no
    # rope base has no positions at all.
    sp = positions = None
    if cache is not None:
        sp = jnp.asarray(start_pos)
        positions = (sp[:, None] if sp.ndim == 1 else sp) + jnp.arange(L)
    def rotate(t):
        if dec.rope_base is None:
            return t
        return rope(t, base=dec.rope_base, positions=positions)
    q = rotate(heads(q, h))
    k = rotate(heads(k, kvh))
    v = heads(v, kvh)
    if cache is None:
        attn = flash_attention(q, _across_group(k, h // kvh),
                               _across_group(v, h // kvh), True,
                               dec.sm_scale)
        new_cache = None
    else:
        attn, new_cache = _cached_attention(q, k, v, cache, sp, h // kvh,
                                            dec.sm_scale)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, d)
    return jnp.einsum("bsd,de->bse", attn, layer["wo"]), new_cache


def mamba2(x, layer, dec: Decoder, cache=None, start_pos=None):
    """The Mamba-2 mixer of x [b, L, d], from the input norm to the
    output projection: one input projection to the gate z, the
    convolved x | B | C and the step sizes; a causal depthwise
    convolution and silu; the selective scan; the gated norm; the output
    projection. One implementation serves training (the chunked scan over
    the whole sequence, no cache), prefill (the same from the cached
    state, returning the final state and the convolution's last inputs)
    and decode (L = 1: one step of the recurrence). The state knows no
    positions: `start_pos` is not read. Returns (y, new_cache or None)."""
    b, L, d = x.shape
    H, P, N, G = dec.ssm_heads, dec.ssm_head_dim, dec.ssm_state, dec.ssm_groups
    inner, bc = H * P, G * N
    y = rms_norm(x, layer["ln1"], dec.norm_eps)
    z, xbc, dt = jnp.split(
        jnp.einsum("bsd,de->bse", y, layer["in_proj"]),
        [inner, 2 * inner + 2 * bc], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc, tail = causal_conv1d(
            xbc, layer["conv_w"], layer["conv_b"],
            None if cache is None else cache["conv"])
        xbc = jax.nn.silu(xbc)
    xs, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
    xs = xs.reshape(b, L, H, P)
    B, C = B.reshape(b, L, G, N), C.reshape(b, L, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    a = -jnp.exp(layer["A_log"].astype(jnp.float32))
    if cache is not None and L == 1:
        f32 = jnp.float32
        x1, dt1 = xs[:, 0].astype(f32), dt[:, 0]               # [b,H,P], [b,H]
        B1, C1 = (jnp.repeat(t[:, 0].astype(f32), H // G, axis=1)
                  for t in (B, C))                             # [b, H, N]
        state = (jnp.exp(dt1 * a)[..., None, None] * cache["ssm"]
                 + (dt1[..., None] * x1)[..., None] * B1[:, :, None, :])
        ys = (jnp.einsum("bhpn,bhn->bhp", state, C1)
              + layer["D"].astype(f32)[:, None] * x1)[:, None].astype(x.dtype)
    else:
        ys, state = ssm_scan(
            xs, dt, a, B, C, layer["D"], dec.ssm_chunk,
            None if cache is None else cache["ssm"])
    with jax.named_scope("ssm_gate_norm"):
        ys = gated_rms_norm(ys.reshape(b, L, inner), z, layer["ssm_norm"],
                            dec.norm_eps)
    new_cache = None if cache is None else {"conv": tail, "ssm": state}
    return jnp.einsum("bse,ed->bsd", ys, layer["out_proj"]), new_cache


# What a rematerialised block keeps for its backward pass, by the names
# `attention` above, ops/attention.py and parallel/moe.py give their
# values (a name lowers to nothing where no jax.checkpoint is round it).
# Kept: what a Pallas kernel or a gather over a permutation made and the
# backward reads (attention's `out` and `lse`; the experts' dispatched rows
# and their gate and up grouped matmuls); what the attention's backward
# reads besides, q, k, v and the projection they are normed and rotated
# from (0.2 GB each at OLMoE's 16,384 tokens, for 4.8 and 4.2 ms a step);
# and the router's probabilities, because rows kept in sorted order must
# meet the same order again (parallel/moe.py). Made again: the block's two
# norms and its output projection, the float32 router up to its
# probabilities, the top-k and sorts, w * silu(gate) * up.
# The routed experts' three are residuals of their one gradient rule
# (parallel/moe.py `_experts`) and of nothing else, so jax.checkpoint puts
# no `reduce_precision` copy after them (6.6 ms a step while the forward
# read the kept values too). Of a layer's [T*k, d] values exactly one is
# kept, the dispatched rows `moe_xs` (537 MB a layer at 16,384 tokens):
# gathered again from the T tokens' rows instead they cost 4.3 ms a
# gather, not the forward's 0.83 (XLA holds the source in fast memory
# there and not here) -- `olmoe-train-1chip`'s step 275.77 ms against
# 267.33 with them kept, for 11.15 against 11.71 GB by XLA's analysis
# (the parent of PR 30: 283.97 ms, 11.69 GB). No experts' output is kept:
# the rule multiplies the router's weights in ahead of the down matmul, so
# its backward needs neither the sorted nor the unsorted rows; with the
# weights after it and the sorted rows kept for their gradient the step
# took 269.83 ms and 12.42 GB (all four: PERF.md §6, PR 30, one chip call,
# the host's clock over 12 steps). What is kept scales with the tokens as
# the block's own live set does, and is alive at the peak anyway, in the
# layer being differentiated: keeping everything does not fit at OLMoE's
# 16,384 tokens (PERF.md §6, PR 28).
# Of a Mamba-2 layer, by the same rule: what the scan kernel made and a
# backward pass reads, the state each chunk left (its backward kernel's)
# and y (the gated norm's, after it) (ops/ssm_scan.py; 0.13 GB each a
# layer at 16,384 tokens of granite-4.0-h-micro). Its input projection,
# convolution, gated norm and output projection are made again.
KEPT_UNDER_REMAT = (
    "attention_qkv", "flash_attention_q", "flash_attention_k",
    "flash_attention_v", "flash_attention_out", "flash_attention_lse",
    "moe_probs", "moe_xs", "moe_gate", "moe_up",
    "ssm_scan_y", "ssm_scan_states")
keep_kernel_outputs = jax.checkpoint_policies.save_only_these_names(
    *KEPT_UNDER_REMAT)


def _scaled(t, scale: float):
    return t if scale == 1.0 else t * scale


def _block(x, layer, cache, start_pos, dec: Decoder):
    mixer = mamba2 if _is_mamba2(layer) else attention
    y, new_cache = mixer(x, layer, dec, cache, start_pos)
    x = x + _scaled(y, dec.residual_scale)
    out, stats = dec.mlp(rms_norm(x, layer["ln2"], dec.norm_eps), layer)
    return x + _scaled(out, dec.residual_scale), stats, new_cache


def decoder_hidden(params: Dict, tokens, dec: Decoder,
                   cache: Optional[List[Dict]] = None, start_pos=None):
    """tokens [b, L] -> (final-norm rows [b, L, d], the output head
    [d, vocab], the mixers' `stats` summed over layers or None, the new
    cache or None). With a `cache` (an `empty_cache`, or the last call's) the
    tokens sit at `start_pos` + [0, L) and the mixers read and write it;
    with none this is the training forward. The rows come multiplied by
    `dec.logit_scale`, so rows @ head are the model's logits."""
    x = _scaled(jnp.take(params["embed"], tokens, axis=0), dec.embed_scale)
    block = functools.partial(_block, dec=dec)
    if dec.remat is not None and cache is None:    # remat is training's
        block = jax.checkpoint(block, policy=dec.remat)
    total, new_cache = None, []
    with jax.named_scope("layers"):
        for layer, cache_layer in zip(
                params["layers"], cache or [None] * len(params["layers"])):
            x, stats, cache_layer = block(x, layer, cache_layer, start_pos)
            total = stats if total is None else jax.tree.map(
                jnp.add, total, stats)
            new_cache.append(cache_layer)
    x = _scaled(rms_norm(x, params["lnf"], dec.norm_eps), dec.logit_scale)
    head = params["head"] if "head" in params else params["embed"].T
    return x, head, total, (new_cache if cache is not None else None)
