"""The decoder's compute, written once: what models.gpt, models.llama and
models.moe train and what models.generate prefills and decodes.

A family says what it is with a `Decoder`, which its config's `decoder()`
builds from the fields it already has: the attention's head counts, its
channel mixer, its remat policy, and the rope base and norm eps where it
names them (ops.layers' own where it does not). Nothing here reads a
config: one family differs from another by those values and by which
weights a layer holds (`wqkv` or `wq` + `wkv`; `q_norm`), and by nothing else.

    decoder_hidden      embedding, layer stack, final norm, head
      attention         the one sequence mixer: with no cache the flash
                        kernel over the whole sequence, with one a write
                        into it and a masked read of it
      gelu_mlp | swiglu_mlp | routed_experts   the channel mixers,
                        (y, layer) -> (out, stats or None)

Cache layout: per layer {"k"|"v": [batch, n_kv_heads, max_len, head_dim]}.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import DEFAULT_MASK_VALUE, flash_attention
from ..ops.layers import NORM_EPS, ROPE_BASE, rms_norm, rope, swiglu
from ..parallel.moe import dropless_moe_layer


class Decoder(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    mlp: Callable                   # (y, layer) -> (out, stats or None)
    remat: Optional[Callable]       # a jax.checkpoint policy; None: keep all
    rope_base: float = ROPE_BASE
    norm_eps: float = NORM_EPS


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


def empty_cache(dec: Decoder, n_layers, batch, max_len, dtype) -> List[Dict]:
    """A layer holds its kv heads, not their copies across a group."""
    shape = (batch, dec.n_kv_heads, max_len, dec.head_dim)
    return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for _ in range(n_layers)]


def _across_group(t, group: int):
    """GQA: each kv head [b, kvh, ., hd] serves its whole query group.
    (GQA inside the kernel, with no expanded copy, is ROADMAP B5's.)"""
    return t if group == 1 else jnp.repeat(t, group, axis=1)


def _cached_attention(q, k, v, cache, sp, group: int):
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

    scale = hd ** -0.5
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
    # [b, L]; with no cache rope counts from 0 itself.
    sp = positions = None
    if cache is not None:
        sp = jnp.asarray(start_pos)
        positions = (sp[:, None] if sp.ndim == 1 else sp) + jnp.arange(L)
    q = rope(heads(q, h), base=dec.rope_base, positions=positions)
    k = rope(heads(k, kvh), base=dec.rope_base, positions=positions)
    v = heads(v, kvh)
    if cache is None:
        attn = flash_attention(q, _across_group(k, h // kvh),
                               _across_group(v, h // kvh), True, None)
        new_cache = None
    else:
        attn, new_cache = _cached_attention(q, k, v, cache, sp, h // kvh)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, d)
    return jnp.einsum("bsd,de->bse", attn, layer["wo"]), new_cache


# What a rematerialised block keeps for its backward pass, by the names
# `attention` above, ops/attention.py and parallel/moe.py give their
# values (a name lowers to nothing where no jax.checkpoint is round it).
# Kept: what a Pallas kernel or a gather over a permutation made and the
# backward reads (attention's `out` and `lse`; the gate and up grouped
# matmuls' rows; the experts' rows back in token order); what the
# attention's backward reads besides, q, k, v and the projection they are
# normed and rotated from (0.2 GB each at OLMoE's 16,384 tokens, for 4.8
# and 4.2 ms a step); and the router's probabilities, because rows kept
# in sorted order must meet the same order again (parallel/moe.py). Made
# again: the block's two norms and its output projection, the float32
# router up to its probabilities, the top-k and sorts, the dispatch
# gather, silu(gate) * up; the down matmul's output is read by nothing
# once the unsorted rows are kept. What is kept scales with the tokens as
# the block's own live set does, and is alive at the peak anyway, in the
# layer being differentiated: 0.5 GB over keeping nothing at OLMoE's
# 16,384 tokens, where keeping everything does not fit (PERF.md §6, PR 28).
KEPT_UNDER_REMAT = (
    "attention_qkv", "flash_attention_q", "flash_attention_k",
    "flash_attention_v", "flash_attention_out", "flash_attention_lse",
    "moe_probs", "moe_gate", "moe_up", "moe_unsorted")
keep_kernel_outputs = jax.checkpoint_policies.save_only_these_names(
    *KEPT_UNDER_REMAT)


def _block(x, layer, cache, start_pos, dec: Decoder):
    y, new_cache = attention(x, layer, dec, cache, start_pos)
    x = x + y
    out, stats = dec.mlp(rms_norm(x, layer["ln2"], dec.norm_eps), layer)
    return x + out, stats, new_cache


def decoder_hidden(params: Dict, tokens, dec: Decoder,
                   cache: Optional[List[Dict]] = None, start_pos=None):
    """tokens [b, L] -> (final-norm rows [b, L, d], the output head
    [d, vocab], the mixers' `stats` summed over layers or None, the new
    cache or None). With a `cache` (an `empty_cache`, or the last call's) the
    tokens sit at `start_pos` + [0, L) and attention reads and writes it;
    with none this is the training forward."""
    x = jnp.take(params["embed"], tokens, axis=0)
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
    x = rms_norm(x, params["lnf"], dec.norm_eps)
    head = params["head"] if "head" in params else params["embed"].T
    return x, head, total, (new_cache if cache is not None else None)
