"""The decoder's compute, written once: what the ten families of
ray_tpu.models train and what models.generate prefills and decodes.

A family says what it is with a `Decoder`, which its config's `decoder()`
builds from the fields it already has: `kinds`, the kind of every layer
in order (from `n_layers`, `layer_types` or `layer_kinds`; nobody sets it);
`mlp`, the channel mixer of every layer in order, as long as `kinds` (the
one function a layer in seven families; LFM2's dense SwiGLU in its leading
layers and its held experts after them); the attention's head counts, its
remat policy, the rope
base (None: no positions at all), the norm eps, the score scale and the
sizes of a state-space mixer where it names them (ops.layers' own,
1/sqrt(head_dim), none where it does not), and what its embedding, its two
residual branches and its logits are multiplied by (1 where it says
nothing). Nothing here reads a config. A model's layers need not be alike:
a layer's kind is a key of `MIXERS`, whose row says which sequence mixer
runs it, what state it keeps in a cache, whether it is windowed, hands its
keys and values on, or reads its place in the stack, and which branches
its block has: a sequence mixer, the layer's channel mixer, or both. A
new sequence mixer is its function and its row. The seventeen kinds:

    ATTENTION      softmax attention; {"k" | "v": [batch, n_kv_heads,
                   max_len, head_dim]}
    WINDOWED_ATTENTION, ATTENTION_NOPE
                   ATTENTION's mixer and state in a stack that mixes the
                   two: the first rotated at `dec.rope_base` and seeing no
                   further back than `dec.window`, the second over every
                   position and with no positions at all, whatever
                   `dec.rope_base` says. Their blocks keep less under remat
                   (KEPT_BY_KIND)
    MAMBA2         the chunked scan; {"conv": [batch, d_conv - 1, inner +
                   2 groups x state] the convolution's last inputs, "ssm":
                   [batch, heads, head_dim, state] float32}, which does
                   not grow
    MAMBA1         the selective scan; {"conv": [batch, d_conv - 1, inner],
                   "ssm": [batch, inner, state] float32}
    GATED_DELTA    the gated delta rule; {"conv": [batch, taps - 1, heads
                   x (2 K + V)] the last inputs over q | k | v, "delta":
                   [batch, heads, K, V] float32}, which does not grow
    SHORT_CONV     the gated short convolution (ops.short_conv); {"conv":
                   [batch, taps - 1, d] the last rows of B * x}
    GMU            a gated memory unit over `Shared.m`; {}
    DIFF_WINDOWED  differential attention over its own keys and values no
                   further back than `dec.window`; ATTENTION's state
    DIFF_FULL      the same over all of them, which it hands on as
                   `Shared.k, v`; ATTENTION's state
    DIFF_CROSS     differential attention of its own queries over
                   `Shared.k, v`; {}
    ATTENTION_ONLY, MAMBA2_ONLY
                   ATTENTION's and MAMBA2's mixer and state in a block of
                   that one branch, x + mixer(norm(x))
    EXPERTS        no sequence mixer at all: a block of the channel mixer
                   alone, x + mlp(norm(x)); {}
    LATENT_ATTENTION
                   softmax attention whose keys and values are made from ONE
                   latent a token (DeepSeek-V3's MLA); {"latent": [batch,
                   max_len, latent width] the normed latent, "k_rope":
                   [batch, max_len, rope width] the rotated key part every
                   head shares}: no per-head K or V is cached
    SPARSE_ATTENTION
                   softmax attention over the `Decoder.sparse_topk` keys a
                   query's own lightning indexer scores highest (DeepSeek
                   sparse attention, ops/sparse_index.py); ATTENTION's
                   state and {"k_index": [batch, max_len, index width] the
                   indexer's rotated keys}. A sequence mixer that
                   hands `stats` out of the stack, its indexer's own loss
                   term `index_loss` and `selected_keys_mean`, in its
                   layer's entry of `decoder_hidden`'s list beside the
                   channel mixer's
    KDA            Kimi Delta Attention, the delta rule with a decay a key
                   channel (ops/kda.py); {"conv": [batch, taps - 1, heads x
                   (2 K + V)] the last inputs over q | k | v, "kda":
                   [batch, heads, K, V] float32}, which does not grow. It
                   hands a counter out of the stack as `stats`,
                   `kda_log_decay_min`

Every leaf of a layer's state has the batch first: that is the table's one
rule (models.generate.make_continuous_fns cuts a slot out of axis 0 of
every leaf). A state's sizes come from `dec` and from the layer's weights
or their shapes, as its mixer's do, so a cache is made from a model's
parameters or from `jax.eval_shape` of its init alike.

What is still read off the weights a layer holds picks no mixer and no
state: `ln1_b`: LayerNorm with bias where the others have RMSNorm; `ln1`,
`ln2`: a block that norms what its branches read, x + mixer(norm(x)),
`post_attention`, `post_feedforward` and neither of those: one that norms
what they return, x + norm(mixer(x)), all four: both (sandwich norms);
`wqkv` or `wq` + `wkv`, `q_norm` (one norm over all of q's columns),
`q_head_norm` (one over each head's) or neither, and `attn_gate` [d, heads
x head_dim]: the kernel's output times sigmoid(y attn_gate), one gate a
channel, before `wo`, inside `attention`; `w_qa`: queries through a normed latent of
their own, inside `latent_attention`; `hc_mixer`, `hc_mlp`: a branch that
reads one learned mix of `dec.hyper.streams` residual streams and writes
back through a doubly stochastic matrix (`hyper_connection`), where a layer
that holds neither adds its branch to the one stream. And off the model's:
`mtp`, a prediction module (DeepSeek-V3's multi-token prediction), which a
training forward that is given the next tokens runs behind the stack
(`prediction_module`) and nothing else reads.

Nor need the layers be independent (SambaY, models.sambay): the stack
carries two values forward besides x, `Shared`: the scan's output `m` of
the last MAMBA1 layer so far, which every GMU after it multiplies, and the
keys and values of the last DIFF_FULL layer, which every DIFF_CROSS layer
after it attends over. Both pass through `jax.checkpoint` as block inputs
and outputs, and their gradients arrive from every reader.

    decoder_hidden      embedding, layer stack, final norm, head
    prediction_module   a multi-token-prediction module behind the stack:
                        the next token's embedding joined to the last
                        block's output, one more block, a norm of its own
    remat_plan          what each rematerialised layer keeps, from shapes
    decoder_logits      its rows times its head, float32
    empty_cache         each layer's state, by its row
    hyper_connection    a hyper-connected branch's three sets of
                        coefficients, from the streams and its weights
      attention | sparse_attention | latent_attention | mamba2 | mamba1 |
      gated_delta | kda | short_conv | gmu | diff_attention
                        the sequence mixers, (x, layer, dec, cache,
                        start_pos[, shared, index, window, ...]) -> (y,
                        new cache[, shared or stats]): attention is the flash
                        kernel over the whole sequence with no cache, with
                        one a write into it and a masked read of it;
                        latent_attention the same, its per-head keys and
                        values made from the (cached) latent first; mamba2
                        the chunked scan (ops.ssm_scan) over the tokens
                        given, from the cached state where there is one,
                        and one step of the recurrence for a single token;
                        mamba1 the same over ops.selective_scan;
                        gated_delta the same over ops.gated_delta, a matrix
                        state a head that is read back before it is
                        written; kda the same over ops.kda, the state's
                        decay a vector a head, one number a key channel;
                        short_conv the kernels over the whole
                        sequence, the shifted products from a cached tail;
                        gmu no state at all; diff_attention two
                        softmax maps a pair of heads, their difference
                        times both heads' values
      gelu_mlp | swiglu_mlp | fused_swiglu_mlp | routed_experts |
      held_routed_experts | held_gated_experts
                        the channel mixers, (y, layer) -> (out, stats or
                        None)
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.extend.core import Var

from ..ops.attention import (DEFAULT_MASK_VALUE, a_chip_alone,
                             attention_and_lse, flash_attention,
                             step_memory_given, step_sharding)
from ..ops.gated_delta import gated_delta_rule
from ..ops.kda import kda_rule
from ..ops.layers import (NORM_EPS, ROPE_BASE, causal_conv1d_silu,
                          gated_rms_norm, head_rms_norm, head_rms_norm_gated,
                          head_spread, head_sums, layer_norm, rms_norm, rope,
                          swiglu)
from ..ops.loss import chip_views, lookup
from ..ops.loss import working_set_bytes as loss_working_set_bytes
from ..ops.selective_scan import selective_scan
from ..ops.short_conv import gated_short_conv
from ..ops.sparse_index import (index_scores, indexer_loss, kth_largest,
                                select)
from ..ops.ssm_scan import ssm_scan
from ..parallel.moe import (dropless_moe_layer, held_backward_bytes,
                            held_moe_layer)


# The kinds of layer: the keys of MIXERS, below the mixers.
(ATTENTION, MAMBA2, MAMBA1, GATED_DELTA, GMU, DIFF_WINDOWED, DIFF_FULL,
 DIFF_CROSS, ATTENTION_ONLY, MAMBA2_ONLY, EXPERTS, SHORT_CONV,
 LATENT_ATTENTION, SPARSE_ATTENTION, KDA, WINDOWED_ATTENTION,
 ATTENTION_NOPE) = (
    "attention", "mamba2", "mamba1", "gated_delta", "gmu", "diff_windowed",
    "diff_full", "diff_cross", "attention_only", "mamba2_only", "experts",
    "short_conv", "latent_attention", "sparse_attention", "kda",
    "windowed_attention", "attention_nope")


class HyperConnections(NamedTuple):
    """The residual path of a model whose blocks are joined by
    manifold-constrained hyper-connections (`hyper_connection`): how many
    streams the stack carries, and the three guards of the mixing matrix
    (config.json's hc_mult, hc_sinkhorn_iters, hc_eps,
    mhc_h_res_clamp_min / _max)."""
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)


class Decoder(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    # a layer's channel mixer, in order: (y, layer) -> (out, stats or None)
    mlp: Tuple[Callable, ...]
    # a jax.checkpoint policy; None: keep all. `keep_kernel_outputs` grows,
    # layer by layer, by what `remat_plan` says fits; any other is as given
    remat: Optional[Callable]
    kinds: Tuple[str, ...]          # a key of MIXERS a layer, in order
    rope_base: Optional[float] = ROPE_BASE     # None: no rotary
    norm_eps: float = NORM_EPS
    sm_scale: Optional[float] = None           # None: 1 / sqrt(head_dim)
    residual_scale: float = 1.0                # on both branches of a block
    embed_scale: float = 1.0
    logit_scale: float = 1.0                   # on the final-norm rows
    # A MAMBA2 layer's sizes: inner width ssm_heads * ssm_head_dim; B and C
    # are ssm_groups * ssm_state wide.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # A GATED_DELTA layer's chunk (its heads and widths are read off its
    # weights).
    delta_chunk: int = 64
    # What a DIFF_WINDOWED or WINDOWED_ATTENTION layer sees: itself and the
    # window - 1 before.
    window: Optional[int] = None
    # The rotated pairs' frequencies where they are not `rope_base`'s own
    # (a scaled context's: ops.layers.yarn_inv_freq), one a pair.
    rope_inv_freq: Optional[Tuple[float, ...]] = None
    # None: one residual stream, every branch added to it. Else the stack
    # carries `hyper.streams` of them, a tuple of so many [b, L, d], from
    # the embedding (each a copy of it) to the final norm (of their sum).
    hyper: Optional[HyperConnections] = None
    # The keys a SPARSE_ATTENTION layer's query may name (its indexer's
    # heads and widths are read off its weights).
    sparse_topk: int = 0
    # The bound a KDA layer's gate keeps a step's log-decay above (its
    # chunk is `delta_chunk`; heads and widths are read off its weights).
    kda_lower_bound: float = -5.0


def gelu_mlp(y, layer):
    # gelu is fused into the matmuls by XLA
    hidden = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, layer["w1"]))
    return jnp.einsum("bsf,fd->bsd", hidden, layer["w2"]), None


def swiglu_mlp(y, layer):
    return swiglu(y, layer["w_gate"], layer["w_up"], layer["w_down"]), None


def fused_swiglu_mlp(y, layer):
    """SwiGLU from one input matrix: [gate | up] = y fc1, the gate first."""
    gate, up = jnp.split(checkpoint_name(
        jnp.einsum("bsd,df->bsf", y, layer["fc1"]), "mlp_gate_up"), 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      layer["fc2"]), None


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


def held_routed_experts(y, layer, experts_per_token: int, first: int,
                        routed_scale: float, bias_rounds: int = 0):
    """One chip's share of top-k experts of two matrices with relu^2
    between (those from `first` on, as many as the layer holds) and the
    shared expert of the same form, over the flattened tokens, the
    selection bias moved `bias_rounds` rounds on their scores first;
    `stats` are parallel.moe.held_moe_layer's, one layer's."""
    b, s, d = y.shape
    out, stats = held_moe_layer(
        y.reshape(b * s, d), layer["router"], layer["router_bias"],
        layer["expert_up"], layer["expert_down"], layer["shared_up"],
        layer["shared_down"], experts_per_token=experts_per_token,
        first=first, routed_scale=routed_scale, bias_rounds=bias_rounds)
    return out.reshape(b, s, d), stats


def held_gated_experts(y, layer, experts_per_token: int, first: int,
                       routed_scale: float, weight_eps: float,
                       bias_rounds: int = 0, softmax: bool = False,
                       n_group: int = 1, topk_group: int = 1):
    """One chip's share of top-k SwiGLU experts (those from `first` on, as
    many as the layer holds; `expert_gate_up` is each one's gate and up
    matrices side by side) and, where the layer holds one, the shared
    SwiGLU expert of the same form (`shared_gate_up`, `shared_down`:
    DeepSeek-V3's; LFM2 has none), over the flattened tokens, the k
    weights over their sum + `weight_eps`, the selection bias moved
    `bias_rounds` rounds on the tokens' scores first, or with `softmax` a
    softmax router with no bias, the layer holding none; with `n_group` >
    1 the k chosen inside the `topk_group` groups a token keeps; `stats`
    are parallel.moe.held_moe_layer's, one layer's."""
    b, s, d = y.shape
    out, stats = held_moe_layer(
        y.reshape(b * s, d), layer["router"], layer.get("router_bias"),
        layer["expert_gate_up"], layer["expert_down"],
        layer.get("shared_gate_up"), layer.get("shared_down"),
        experts_per_token=experts_per_token, first=first,
        routed_scale=routed_scale, bias_rounds=bias_rounds, gated=True,
        weight_eps=weight_eps, **({"softmax": True} if softmax else {}),
        **({"n_group": n_group, "topk_group": topk_group}
           if n_group > 1 else {}))
    return out.reshape(b, s, d), stats


def _norm(x, holder, name: str, eps: float):
    """The norm whose weight `holder` has under `name`: LayerNorm where
    it has a bias beside it, RMSNorm where not."""
    if name + "_b" in holder:
        return layer_norm(x, holder[name], holder[name + "_b"], eps)
    return rms_norm(x, holder[name], eps)


def _norm_if_held(x, layer, name: str, eps: float):
    """`_norm` where the layer holds the weight, x as it is where not. A
    block norms what a branch READS (`ln1`, `ln2`) or what it RETURNS
    (`post_attention`, `post_feedforward`: OLMo 2's order), and says which
    by the weights it holds."""
    return _norm(x, layer, name, eps) if name in layer else x


def _across_group(t, group: int):
    """GQA: each kv head [b, kvh, ., hd] serves its whole query group.
    (GQA inside the kernels, with no expanded copy, is in ROADMAP's list
    of what the system cannot run yet.)"""
    return t if group == 1 else jnp.repeat(t, group, axis=1)


def _write_cache(cache, k, v, sp):
    """k, v [b, kvh, L, hd] written into the cache at positions sp +
    [0, L), `sp` a scalar or one position a row."""
    b, _, L, _ = k.shape
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
    return {"k": k_cache, "v": v_cache}


def _cache_mask(L: int, max_len: int, sp, window=None):
    """Which of `max_len` cached positions the queries at sp + [0, L) see,
    each up to its own (and, under a `window`, no further back than window
    - 1): [1 or b, L, max_len] bool, `sp` a scalar or one position a
    row."""
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 1)
    q_pos = (sp[:, None, None] if sp.ndim == 1 else sp) + q_iota[None]
    mask = k_pos[None] <= q_pos
    if window is not None:
        mask &= k_pos[None] > q_pos - window
    return mask


def _attend_cache(q, k_all, v_all, sp, sm_scale, window=None, selected=None):
    """q [b, h, L, hd] at positions sp + [0, L) over k_all, v_all
    [b, h, max_len, .], each query up to its own position (and, under a
    `window`, no further back than window - 1; of those, where `selected`
    [b, L, max_len] bool is given, the ones it names): plain masked
    softmax."""
    L, hd = q.shape[-2:]
    max_len = k_all.shape[-2]
    scale = hd ** -0.5 if sm_scale is None else sm_scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    mask = _cache_mask(L, max_len, sp, window)
    if selected is not None:
        mask = mask & selected
    s = jnp.where(mask[:, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_all.dtype), v_all)


def _cached_attention(q, k, v, cache, sp, group: int, sm_scale,
                      selected=None, window=None):
    """Write k, v [b, kvh, L, hd] into the cache at positions sp + [0, L)
    and attend q [b, h, L, hd] over the cache up to each query's own
    position (under a `window` no further back than window - 1; of those,
    the `selected` ones where given). Only the write and the mask
    specialize on whether `sp` is a scalar or one position a row."""
    new_cache = _write_cache(cache, k, v, sp)
    attn = _attend_cache(q, _across_group(new_cache["k"], group),
                         _across_group(new_cache["v"], group), sp, sm_scale,
                         window, selected)
    return attn, new_cache


def _qkv_heads(x, layer, dec: Decoder, cache, start_pos):
    """What `attention` and `sparse_attention` share, from the input norm
    to the rotated heads: (y the normed input [b, L, d], q [b, h, L, hd],
    k and v [b, kvh, L, hd], sp the cache's start position as an array or
    None, the rows' absolute positions or None: rope then counts from 0
    itself)."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim

    def heads(t, n):
        return t.reshape(b, L, n, hd).transpose(0, 2, 1, 3)

    y = _norm_if_held(x, layer, "ln1", dec.norm_eps)
    if "wqkv" in layer:
        q, k, v = jnp.split(checkpoint_name(
            jnp.einsum("bsd,de->bse", y, layer["wqkv"]), "attention_qkv"),
            3, axis=-1)
    else:
        q = checkpoint_name(
            jnp.einsum("bsd,de->bse", y, layer["wq"]), "attention_q_proj")
        k, v = jnp.split(checkpoint_name(
            jnp.einsum("bsd,de->bse", y, layer["wkv"]), "attention_kv_proj"),
            2, axis=-1)
    if "q_norm" in layer:           # over all of q and of k, before the split
        q = rms_norm(q, layer["q_norm"], dec.norm_eps)
        k = rms_norm(k, layer["k_norm"], dec.norm_eps)
    if "q_head_norm" in layer:      # over each head's columns, one [hd] weight
        q = head_rms_norm(q, layer["q_head_norm"], dec.norm_eps)
        k = head_rms_norm(k, layer["k_head_norm"], dec.norm_eps)
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
    q, k = rotate(heads(q, h)), rotate(heads(k, kvh))
    if "wqkv" not in layer:         # at kv-head width, before any group's copy
        k = checkpoint_name(k, "attention_k_heads")
    return y, q, k, heads(v, kvh), sp, positions


def attention(x, layer, dec: Decoder, cache=None, start_pos=None,
              window: Optional[int] = None):
    """Causal self-attention of x [b, L, d], from the input norm to the
    output projection. With a cache, `start_pos` is the absolute offset
    of x's positions — a scalar (all rows aligned: prefill / single-stream
    decode) or a [b] vector (continuous batching: every row decodes at its
    own position). Under a `window` a query sees itself and the window - 1
    positions before it (the kernels' band; with a cache the read's mask).
    Where the layer holds `attn_gate` [d, h hd] the heads' outputs are
    multiplied by sigmoid(y attn_gate), y the normed input, one gate a
    channel, before the output projection. One implementation serves
    training, prefill and decode so the formulas can't diverge. Returns (y,
    new_cache or None)."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim
    y, q, k, v, sp, _ = _qkv_heads(x, layer, dec, cache, start_pos)
    if cache is None:
        attn = flash_attention(q, _across_group(k, h // kvh),
                               _across_group(v, h // kvh), True,
                               dec.sm_scale, window)
        new_cache = None
    else:
        attn, new_cache = _cached_attention(q, k, v, cache, sp, h // kvh,
                                            dec.sm_scale, window=window)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, h * hd)
    if "attn_gate" in layer:
        with jax.named_scope("attention_gate"):
            attn = _gated_by_channel(attn, y, layer["attn_gate"])
    return jnp.einsum("bsd,de->bse", attn, layer["wo"]), new_cache


def _gated_by_channel(t, y, w_gate):
    """t [b, L, w] times sigmoid(y w_gate) [b, L, w], one gate a channel,
    the sigmoid and the product in float32. The projection is named: what
    a rematerialised block may keep of the gate (FITS_BY_KIND)."""
    gate = checkpoint_name(jnp.einsum("bsd,de->bse", y, w_gate),
                           "attention_gate_proj")
    return (t.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(t.dtype)


# The eps of an indexer's key norm (a LayerNorm of gain and bias).
INDEX_NORM_EPS = 1e-6


def _detached(y):
    """What an indexer reads of the block's normed input: its value alone."""
    return jax.lax.stop_gradient(y)


def _index_heads(y, layer, dec: Decoder, positions):
    """A lightning indexer's three projections of the normed input y [b, L,
    d], which its caller has DETACHED (`_detached`): q_I [b, H, L, D] = y `index_wq`, rotated; k_I [b, L, D] =
    layernorm(y `index_wk`; `index_k_norm`, `index_k_norm_b`), rotated; w
    [b, L, H] float32 = y `index_ww` / sqrt(H D). Rotary over all D
    columns at the model's base; H and D are read off the weights."""
    b, L, _ = y.shape
    H, D = layer["index_ww"].shape[1], layer["index_wk"].shape[1]

    def rotate(t):
        if dec.rope_base is None:
            return t
        return rope(t, base=dec.rope_base, positions=positions)

    with jax.named_scope("sparse_index_proj"):
        q = rotate(jnp.einsum("bsd,de->bse", y, layer["index_wq"]).reshape(
            b, L, H, D).transpose(0, 2, 1, 3))
        k = layer_norm(jnp.einsum("bsd,de->bse", y, layer["index_wk"]),
                       layer["index_k_norm"], layer["index_k_norm_b"],
                       INDEX_NORM_EPS)
        k = rotate(k[:, None])[:, 0]
        w = jnp.einsum("bsd,dh->bsh", y, layer["index_ww"],
                       preferred_element_type=jnp.float32) * (H * D) ** -0.5
    return q, k, w


def _cache_index_scores(q_index, k_index, w):
    """The index scores of q_index [b, H, L, D] and w [b, L, H] against
    EVERY cached key k_index [b, max_len, D] -> [b, L, max_len] float32:
    the plain form, for a cache's forward (ops.sparse_index has a whole
    sequence's)."""
    s = jnp.einsum("bhld,bmd->bhlm", q_index, k_index,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("blh,bhlm->blm", w, jnp.maximum(s, 0.0))


def sparse_attention(x, layer, dec: Decoder, cache=None, start_pos=None):
    """`attention` over a learned sparse subset of positions (DeepSeek
    sparse attention), from the input norm to the output projection: the
    same projections, head norms and rotary, then each query sees the
    `dec.sparse_topk` keys at or before it that its layer's lightning
    indexer scores highest (ops/sparse_index.py has the equations), every
    head the same ones, a key head under its group of query heads. The
    indexer reads the normed input DETACHED and its selection has no
    gradient: the cross entropy reaches none of its weights, and its own
    loss L_I (the KL from the heads' mean attention probability over the
    selected keys, itself detached, to the softmax of the index scores
    there) reaches nothing else. Training: `index_scores`, `select`, the
    flash kernels under the selection, `indexer_loss`. With a cache the
    rotated index key of x's positions is written beside k and v, every
    cached position is scored in the plain form and the read is masked by
    the selection. Returns (y, new_cache or None, {`index_loss`: L_I (0
    with a cache), `selected_keys_mean`: the keys a query saw})."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim
    y, q, k, v, sp, positions = _qkv_heads(x, layer, dec, cache, start_pos)
    q_index, k_index, w = _index_heads(_detached(y), layer, dec, positions)
    # What a rematerialised block keeps of the selection's making, beside
    # the selection itself: the rotated index key (`indexer_loss`'s rule
    # keeps its own three gradients).
    k_index = checkpoint_name(k_index, "sparse_index_k")
    if cache is None:
        # Read, not differentiated: `indexer_loss` is the indexer's rule.
        scores = index_scores(*jax.lax.stop_gradient((q_index, k_index, w)))
        selected = checkpoint_name(select(scores, dec.sparse_topk)[0],
                                   "sparse_selected")
        attn, lse = attention_and_lse(
            q, _across_group(k, h // kvh), _across_group(v, h // kvh),
            dec.sm_scale, selected)
        scale = hd ** -0.5 if dec.sm_scale is None else dec.sm_scale
        # (q, k and lse are read: the rule hands them no cotangent)
        index_loss = indexer_loss(q_index, k_index, w, scores, selected, q,
                                  k, lse, scale)
        seen = jnp.sum(selected, dtype=jnp.float32) / (b * L)
        new_cache = None
    else:
        k_index = _write_rows(cache["k_index"], k_index, sp)
        with jax.named_scope("sparse_select"):
            causal = _cache_mask(L, k_index.shape[1], sp)
            scores = jnp.where(
                causal, _cache_index_scores(q_index, k_index, w), -jnp.inf)
            selected = causal & (
                scores >= kth_largest(scores, dec.sparse_topk)[..., None])
        attn, new_cache = _cached_attention(q, k, v, cache, sp, h // kvh,
                                            dec.sm_scale, selected)
        new_cache["k_index"] = k_index
        index_loss = jnp.zeros((), jnp.float32)
        seen = jnp.mean(jnp.sum(selected, axis=-1, dtype=jnp.float32))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, h * hd)
    # (named: the channel branch reads x + this, so a rematerialised block
    # that does not keep it runs the output projection again)
    out = checkpoint_name(jnp.einsum("bsd,de->bse", attn, layer["wo"]),
                          "sparse_attention_out")
    return (out, new_cache,
            {"index_loss": index_loss, "selected_keys_mean": seen})


# The eps of the norms over a latent (the keys' and the queries'): the
# published family code builds them with its class's default, not the
# config's `rms_norm_eps`.
LATENT_NORM_EPS = 1e-6


def _latent_sizes(layer, heads: int):
    """(latent width, rope width, a head's no-rope width, a head's value
    width) of a latent-attention layer, off its weights (or their shapes):
    `w_kva` is latent | shared rope key side by side, `wq` (or, behind a
    query latent, `w_qb`) a head's no-rope | rope columns, `w_kvb` a head's
    no-rope key | value columns."""
    latent = layer["latent_norm"].shape[0]
    r = layer["w_kva"].shape[1] - latent
    wq = layer["w_qb" if "w_qa" in layer else "wq"]
    n = wq.shape[1] // heads - r
    return latent, r, n, layer["w_kvb"].shape[1] // heads - n


def _from_latent(latent, k_rope, layer, heads: int, n: int):
    """Per-head keys and values of the positions whose normed `latent`
    [b, m, c] and rotated shared key `k_rope` [b, m, r] are given: [k_n | v]
    a head = latent W_kvb, k = [k_n | k_rope] with the one rotated key
    under every head -> (k [b, h, m, n + r], v [b, h, m, vd])."""
    b, m, r = k_rope.shape
    kv = jnp.einsum("bmc,ce->bme", latent, layer["w_kvb"]).reshape(
        b, m, heads, -1).transpose(0, 2, 1, 3)
    k_n, v = jnp.split(kv, [n], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_rope[:, None], (b, heads, m, r))], axis=-1)
    return k, v


def _write_rows(buffer, rows, sp):
    """rows [b, L, w] written into buffer [b, max_len, w] at positions sp +
    [0, L), `sp` a scalar or one position a row."""
    rows = rows.astype(buffer.dtype)
    if sp.ndim == 1:
        b, L, _ = rows.shape
        return buffer.at[jnp.arange(b)[:, None],
                         sp[:, None] + jnp.arange(L)[None]].set(rows)
    return jax.lax.dynamic_update_slice(buffer, rows, (0, sp, 0))


def latent_attention(x, layer, dec: Decoder, cache=None, start_pos=None):
    """DeepSeek-V3's multi-head latent attention of x [b, L, d], from the
    input norm to the output projection: q = y W_q or, where the layer
    holds `w_qa`, through a latent of the queries' own, q = rmsnorm(y W_qa;
    `q_latent_norm`) W_qb; a head [q_n | q_r]; [c | k_r] = y W_kva, ONE
    latent c and ONE rope key k_r a token; c normed (`latent_norm`; both
    latents' norms at LATENT_NORM_EPS); q_r and k_r rotated over their own
    columns at `dec.rope_base`, or at `dec.rope_inv_freq` where the context
    is a scaled one, k_r the same under every head; a head's [k_n | v] =
    c W_kvb; scores q . [k_n | k_r] / sqrt(n + r) unless `dec.sm_scale`
    says otherwise (YaRN's carries its temperature squared); out =
    concat(P v) W_o, or where the layer holds `head_gate` [d, heads]
    (concat(P v) * sigmoid(y W_g), one gate a head) W_o. Every width is
    read off the weights. One
    implementation serves training (the flash kernel, q and k wider than
    v), prefill and decode: with a cache the normed latent and the rotated
    key of x's positions are written into it, K and V of ALL cached
    positions are made from the latent by W_kvb, and the read is masked
    (the plain form; folding W_kvb into q and o is ROADMAP's). Returns (y,
    new_cache or None)."""
    b, L, d = x.shape
    h = dec.n_heads
    latent, r, n, vd = _latent_sizes(layer, h)
    y = _norm(x, layer, "ln1", dec.norm_eps)
    sp = positions = None
    if cache is not None:
        sp = jnp.asarray(start_pos)
        positions = (sp[:, None] if sp.ndim == 1 else sp) + jnp.arange(L)

    def rotate(t):
        return rope(t, base=dec.rope_base, positions=positions,
                    inv_freq=dec.rope_inv_freq)

    with jax.named_scope("mla_project"):
        if "w_qa" in layer:
            q = jnp.einsum("bsc,ce->bse", rms_norm(
                jnp.einsum("bsd,dc->bsc", y, layer["w_qa"]),
                layer["q_latent_norm"], LATENT_NORM_EPS), layer["w_qb"])
        else:
            q = jnp.einsum("bsd,de->bse", y, layer["wq"])
        q_n, q_r = jnp.split(
            q.reshape(b, L, h, n + r).transpose(0, 2, 1, 3), [n], axis=-1)
        q = jnp.concatenate([q_n, rotate(q_r)], axis=-1)
        c, k_r = jnp.split(jnp.einsum("bsd,de->bse", y, layer["w_kva"]),
                           [latent], axis=-1)
        # What a rematerialised block keeps of its keys and values: latent
        # + rope widths a token, from which `_from_latent` makes them again.
        c = checkpoint_name(
            rms_norm(c, layer["latent_norm"], LATENT_NORM_EPS), "mla_latent")
        k_r = checkpoint_name(rotate(k_r[:, None])[:, 0], "mla_k_rope")
    new_cache = None
    if cache is not None:
        new_cache = {"latent": _write_rows(cache["latent"], c, sp),
                     "k_rope": _write_rows(cache["k_rope"], k_r, sp)}
        c, k_r = new_cache["latent"], new_cache["k_rope"]
    with jax.named_scope("mla_expand"):
        k, v = _from_latent(c, k_r, layer, h, n)
    if cache is None:
        attn = flash_attention(q, k, v, True, dec.sm_scale)
    else:
        attn = _attend_cache(q, k, v, sp, dec.sm_scale)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, h * vd)
    if "head_gate" in layer:
        with jax.named_scope("mla_gate"):
            attn = _gated_by_head(attn, y, layer["head_gate"])
    return jnp.einsum("bsd,de->bse", attn, layer["wo"]), new_cache


def _gated_by_head(t, y, w_gate):
    """t [b, L, heads * W] times sigmoid(y w_gate) [b, L, heads], one gate
    a head on all of its columns, in float32 and t's own layout."""
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", y, w_gate,
                                     preferred_element_type=jnp.float32))
    return (t.astype(jnp.float32)
            * head_spread(gate, t.shape[-1])).astype(t.dtype)


def mamba2(x, layer, dec: Decoder, cache=None, start_pos=None):
    """The Mamba-2 mixer of x [b, L, d], from the input norm to the
    output projection: one input projection to the gate z, the
    convolved x | B | C and the step sizes; a causal depthwise
    convolution and silu; the selective scan; the gated norm, one norm a
    group of B and C; the output projection. One implementation serves
    training (the chunked scan over
    the whole sequence, no cache), prefill (the same from the cached
    state, returning the final state and the convolution's last inputs)
    and decode (L = 1: one step of the recurrence). The state knows no
    positions: `start_pos` is not read. Returns (y, new_cache or None)."""
    b, L, d = x.shape
    H, P, N, G = dec.ssm_heads, dec.ssm_head_dim, dec.ssm_state, dec.ssm_groups
    inner, bc = H * P, G * N
    y = _norm(x, layer, "ln1", dec.norm_eps)
    z, xbc, dt = jnp.split(checkpoint_name(
        jnp.einsum("bsd,de->bse", y, layer["in_proj"]), "ssm_in_proj"),
        [inner, 2 * inner + 2 * bc], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc, tail = causal_conv1d_silu(
            xbc, layer["conv_w"], layer["conv_b"],
            None if cache is None else cache["conv"])
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
        ys = checkpoint_name(
            gated_rms_norm(ys.reshape(b, L, inner), z, layer["ssm_norm"],
                           dec.norm_eps, G), "ssm_gated")
    new_cache = None if cache is None else {"conv": tail, "ssm": state}
    return jnp.einsum("bse,ed->bsd", ys, layer["out_proj"]), new_cache


def _delta_sizes(layer):
    """(heads, key width, value width) of a gated-delta-rule layer, off
    its weights (or their shapes): `delta_in` is q | k | v side by side."""
    H, V = layer["A_log"].shape[0], layer["delta_norm"].shape[0]
    return H, (layer["delta_in"].shape[1] // H - V) // 2, V


def _unit_heads(t, heads: int, scale: float, eps: float):
    """t [b, L, heads * K] -> [b, L, heads, K], each head's K columns
    divided by sqrt(their sum of squares + eps) and multiplied by `scale`,
    in float32, in t's own layout (ops.layers.head_sums tells why)."""
    b, L, width = t.shape
    tf = t.astype(jnp.float32)
    inv = jax.lax.rsqrt(head_sums(jnp.square(tf), heads) + eps) * scale
    return (tf * head_spread(inv, width)).astype(t.dtype).reshape(
        b, L, heads, -1)


def _delta_rule_step(state, decay, q, k, v, beta):
    """One token of a delta rule, a decode step's: state [b, H, K, V]
    float32 times `decay` (a head's [b, H, 1, 1], or a key channel's [b, H,
    K, 1]), read back, corrected and written; q, k [b, 1, H, K], v [b, 1,
    H, V], beta [b, 1, H] -> (o [b, 1, H, V] in v's dtype, the new
    state)."""
    q1, k1, v1 = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    state = decay * state
    u = beta[:, 0, :, None] * (v1 - jnp.einsum("bhkv,bhk->bhv", state, k1))
    state = state + k1[..., :, None] * u[..., None, :]
    return (jnp.einsum("bhkv,bhk->bhv", state, q1)[:, None].astype(v.dtype),
            state)


def gated_delta(x, layer, dec: Decoder, cache=None, start_pos=None):
    """The Gated DeltaNet mixer of x [b, L, d], from the input norm to the
    output projection: one projection to q | k | v, a causal depthwise
    convolution with no bias and silu over all of them, q and k
    L2-normalised a head (q scaled by 1 / sqrt(key width) besides), beta =
    2 sigmoid(.) in (0, 2) and g = -exp(A_log) softplus(. + dt_bias) <= 0 a
    head from `delta_ab`, the delta rule (ops.gated_delta), an RMSNorm a
    head THEN the gate silu(y W_g), the output projection. Training,
    prefill from a cached state and decode (L = 1: one step of the
    recurrence) as `mamba2`; `start_pos` is not read. Returns (y, new_cache
    or None)."""
    b, L, d = x.shape
    H, K, V = _delta_sizes(layer)
    f32 = jnp.float32
    y = _norm_if_held(x, layer, "ln1", dec.norm_eps)
    qkv = checkpoint_name(jnp.einsum("bsd,de->bse", y, layer["delta_in"]),
                          "gated_delta_in")
    with jax.named_scope("ssm_conv"):
        qkv, tail = causal_conv1d_silu(
            qkv, layer["conv_w"], None,
            None if cache is None else cache["conv"])
    q, k, v = jnp.split(qkv, [H * K, 2 * H * K], axis=-1)
    with jax.named_scope("delta_qk_norm"):
        q = _unit_heads(q, H, K ** -0.5, dec.norm_eps)
        k = _unit_heads(k, H, 1.0, dec.norm_eps)
    v = v.reshape(b, L, H, V)
    a, bt = jnp.split(jnp.einsum("bsd,de->bse", y, layer["delta_ab"],
                                 preferred_element_type=f32), 2, axis=-1)
    beta = 2.0 * jax.nn.sigmoid(bt)
    g = -jnp.exp(layer["A_log"].astype(f32)) * jax.nn.softplus(
        a + layer["dt_bias"])
    if cache is not None and L == 1:
        o, state = _delta_rule_step(
            cache["delta"], jnp.exp(g[:, 0])[..., None, None], q, k, v, beta)
    else:
        o, state = gated_delta_rule(
            q, k, v, g, beta, dec.delta_chunk,
            None if cache is None else cache["delta"])
    gate = checkpoint_name(
        jnp.einsum("bsd,de->bse", y, layer["delta_gate"]), "gated_delta_in")
    with jax.named_scope("delta_gate_norm"):
        o = head_rms_norm_gated(o, gate, layer["delta_norm"], dec.norm_eps)
    new_cache = None if cache is None else {"conv": tail, "delta": state}
    return jnp.einsum("bse,ed->bsd", o, layer["delta_out"]), new_cache


def _kda_sizes(layer):
    """(heads, key width, value width) of a KDA layer, off its weights (or
    their shapes): `kda_in` is q | k | v side by side, `kda_f` the decay's
    projection, a key's width a head."""
    H, V = layer["A_log"].shape[0], layer["kda_norm"].shape[0]
    return H, layer["kda_f"].shape[1] // H, V


def kda(x, layer, dec: Decoder, cache=None, start_pos=None):
    """The Kimi Delta Attention mixer of x [b, L, d] (arXiv:2510.26692),
    from the input norm to the output projection: one projection to q | k |
    v, a causal depthwise convolution with no bias and silu over all of
    them, q and k L2-normalised a head (q scaled by 1 / sqrt(key width)
    besides), beta = sigmoid(y W_beta) in (0, 1) a head, the log-decay ONE
    NUMBER A KEY CHANNEL, g = `dec.kda_lower_bound` sigmoid(exp(A_log_h) (y
    W_f + dt_bias)) in (bound, 0) (flash-linear-attention's lower-bounded
    gate, float32), the delta rule (ops.kda), an RMSNorm a head THEN one
    sigmoid gate a head (`head_gate`), the output projection. Training,
    prefill from a cached state and decode (L = 1: one step of the
    recurrence) as `gated_delta`, whose convolution, L2 norm and head norm
    these are; `start_pos` is not read. Returns (y, new_cache or None,
    {"kda_log_decay_min": the smallest g of the call})."""
    b, L, d = x.shape
    H, K, V = _kda_sizes(layer)
    f32 = jnp.float32
    y = _norm_if_held(x, layer, "ln1", dec.norm_eps)
    qkv = checkpoint_name(jnp.einsum("bsd,de->bse", y, layer["kda_in"]),
                          "kda_in")
    with jax.named_scope("ssm_conv"):
        qkv, tail = causal_conv1d_silu(
            qkv, layer["conv_w"], None,
            None if cache is None else cache["conv"])
    q, k, v = jnp.split(qkv, [H * K, 2 * H * K], axis=-1)
    with jax.named_scope("kda_qk_norm"):
        q = _unit_heads(q, H, K ** -0.5, dec.norm_eps)
        k = _unit_heads(k, H, 1.0, dec.norm_eps)
    v = v.reshape(b, L, H, V)
    with jax.named_scope("kda_gate"):
        f = jnp.einsum("bsd,de->bse", y, layer["kda_f"],
                       preferred_element_type=f32) + layer["dt_bias"]
        rate = jnp.repeat(jnp.exp(layer["A_log"].astype(f32)), K)
        g = checkpoint_name(
            dec.kda_lower_bound * jax.nn.sigmoid(rate * f), "kda_g")
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", y, layer["kda_beta"], preferred_element_type=f32))
    g = g.reshape(b, L, H, K)
    if cache is not None and L == 1:
        o, state = _delta_rule_step(
            cache["kda"], jnp.exp(g[:, 0])[..., None], q, k, v, beta)
    else:
        o, state = kda_rule(
            q, k, v, g, beta, dec.delta_chunk,
            None if cache is None else cache["kda"], dec.kda_lower_bound)
    with jax.named_scope("kda_gate_norm"):
        o = _gated_by_head(
            head_rms_norm(o.reshape(b, L, H * V), layer["kda_norm"],
                          dec.norm_eps), y, layer["head_gate"])
    new_cache = None if cache is None else {"conv": tail, "kda": state}
    return (jnp.einsum("bse,ed->bsd", o, layer["kda_out"]), new_cache,
            {"kda_log_decay_min": jnp.min(g)})


def short_conv(x, layer, dec: Decoder, cache=None, start_pos=None):
    """LFM2's gated short convolution of x [b, L, d], from the input norm
    to the output projection: one projection to B | C | x, the causal
    depthwise convolution of B * x under `conv_taps` [taps, d] with no
    bias and no activation, times C (ops.short_conv: two kernels over a
    whole sequence; the shifted products from a cache's tail, a prefill or
    a single token alike), the output projection. The state is the last
    taps - 1 rows of B * x and knows no positions: `start_pos` is not
    read. Returns (y, new_cache or None)."""
    y = _norm(x, layer, "ln1", dec.norm_eps)
    with jax.named_scope("short_conv_proj"):
        bcx = checkpoint_name(
            jnp.einsum("bsd,de->bse", y, layer["conv_in"]), "short_conv_in")
    mixed, tail = gated_short_conv(
        bcx, layer["conv_taps"], None if cache is None else cache["conv"])
    with jax.named_scope("short_conv_proj"):
        out = jnp.einsum("bsd,de->bse", mixed, layer["conv_out"])
    return out, None if cache is None else {"conv": tail}


class Shared(NamedTuple):
    """What the layer stack carries forward besides x (None: no layer has
    made it yet): `m` [b, L, inner], the scan's output of the last Mamba-1
    layer, before its gate; `k`, `v` [b, kvh, ., hd], the keys and values
    of the last differential-attention layer that saw the whole sequence
    (with a cache, that layer's cache)."""
    m: Optional[jax.Array] = None
    k: Optional[jax.Array] = None
    v: Optional[jax.Array] = None


def mamba1(x, layer, dec: Decoder, cache=None, start_pos=None):
    """The Mamba-1 mixer of x [b, L, d], from the input norm to the
    output projection: the input projection to x | z, a causal depthwise
    convolution and silu, x_proj to the low-rank step | B | C, dt_proj and
    softplus to a step a channel, the selective scan with a decay of its
    own for every (channel, state) pair, the gate, the output projection.
    Every size is read off the weights. Training, prefill and decode as
    `mamba2`. Returns (y, new_cache or None, the scan's output m before
    the gate: what the gated memory units after it read)."""
    L = x.shape[1]
    N = layer["A_log"].shape[1]
    rank = layer["dt_proj"].shape[0]
    y = _norm(x, layer, "ln1", dec.norm_eps)
    xs, z = jnp.split(checkpoint_name(
        jnp.einsum("bsd,de->bse", y, layer["in_proj"]), "ssm_in_proj"), 2,
        axis=-1)
    with jax.named_scope("ssm_conv"):
        xs, tail = causal_conv1d_silu(
            xs, layer["conv_w"], layer["conv_b"],
            None if cache is None else cache["conv"])
    low, B, C = jnp.split(jnp.einsum("bse,er->bsr", xs, layer["x_proj"]),
                          [rank, rank + N], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", low, layer["dt_proj"],
                   preferred_element_type=jnp.float32) + layer["dt_bias"])
    a = -jnp.exp(layer["A_log"].astype(jnp.float32))
    if cache is not None and L == 1:
        f32 = jnp.float32
        x1, dt1 = xs[:, 0].astype(f32), dt[:, 0]               # [b, inner]
        state = (jnp.exp(dt1[..., None] * a) * cache["ssm"]
                 + (dt1 * x1)[..., None] * B[:, 0].astype(f32)[:, None, :])
        m = (jnp.einsum("bcn,bn->bc", state, C[:, 0].astype(f32))
             + layer["D"].astype(f32) * x1)[:, None].astype(x.dtype)
    else:
        m, state = selective_scan(
            xs, dt, a, B, C, layer["D"],
            None if cache is None else cache["ssm"])
    new_cache = None if cache is None else {"conv": tail, "ssm": state}
    gated = m * jax.nn.silu(z)
    return jnp.einsum("bse,ed->bsd", gated, layer["out_proj"]), new_cache, m


def gmu(x, layer, dec: Decoder, m):
    """A gated memory unit over x [b, L, d]: the scan's output `m` of an
    earlier Mamba-1 layer, at the same tokens, gated by this layer's own
    projection of x. No scan, no convolution, no state."""
    y = _norm(x, layer, "ln1", dec.norm_eps)
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(jnp.einsum("bsd,de->bse", y, layer["gmu_in"]))
        return jnp.einsum("bse,ed->bsd", gate * m, layer["gmu_out"])


def diff_lambda_init(index: int) -> float:
    """Differential attention's lambda at initialisation, by the layer's
    place in the stack."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def diff_attention(x, layer, dec: Decoder, cache, start_pos, shared: Shared,
                   index: int, window: Optional[int], own, hands_on):
    """Differential attention of x [b, L, d], from the input norm to the
    output projection, no positions at all. Heads pair up neighbours:
    query pair p is heads 2p, 2p + 1; kv pair g = p // group is k heads
    2g, 2g + 1 and V_g = [v_2g | v_2g+1], twice as wide. O_p = (softmax(
    q_2p k_2g^T) - lambda softmax(q_2p+1 k_2g+1^T)) V_g, one RMSNorm over
    its 2 hd columns (one weight a layer), times 1 - lambda_init. Each
    score map is computed once: the flash kernel takes v wider than q and
    k, so head 2p + e is q_2p+e, k_2g+e and V_g, and the subtraction comes
    after (`diff_attention_combine`). An `own` layer attends over its own
    keys and values from `wqkv` (under `window`, or all of them), which
    one that `hands_on` puts in `shared`; any other over `shared`'s, from
    `wq` alone. Returns (y, new_cache or None, shared)."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim
    y = _norm(x, layer, "ln1", dec.norm_eps)
    sp = None if cache is None else jnp.asarray(start_pos)

    def heads(t, n):
        return t.reshape(b, L, n, hd).transpose(0, 2, 1, 3)

    new_cache = cache
    if own:
        q, k, v = jnp.split(
            jnp.einsum("bsd,de->bse", y, layer["wqkv"]) + layer["bqkv"],
            [h * hd, (h + kvh) * hd], axis=-1)
        k, v = heads(k, kvh), heads(v, kvh)
        if cache is not None:
            new_cache = _write_cache(cache, k, v, sp)
            k, v = new_cache["k"], new_cache["v"]
        if hands_on:
            shared = shared._replace(k=k, v=v)
    else:
        q = jnp.einsum("bsd,de->bse", y, layer["wq"]) + layer["bq"]
        k, v = shared.k, shared.v
    out = differential_maps(heads(q, h), k, v, layer, dec, index, window, sp)
    return (jnp.einsum("bsd,de->bse", out, layer["wo"]) + layer["bo"],
            new_cache, shared)


def differential_maps(q, k, v, layer, dec: Decoder, index: int,
                      window: Optional[int], sp=None):
    """Differential attention between the projections: q [b, h, L, hd]
    over k, v [b, kvh, n, hd] -> [b, L, h hd], `layer` holding the four
    lambda vectors and the sub-norm's weight. The flash kernel over the
    whole sequence, or (`sp`, the queries' positions: with a cache) a
    masked read of all n cached positions."""
    b, h, L, hd = q.shape
    kvh, n = k.shape[1:3]
    # head 2p + e of the 2 x pairs maps: k head 2g + e, and V_g.
    group = h // kvh
    k_all = jnp.repeat(k.reshape(b, kvh // 2, 2, n, hd), group,
                       axis=1).reshape(b, h, n, hd)
    v_all = jnp.repeat(
        v.reshape(b, kvh // 2, 2, n, hd).transpose(0, 1, 3, 2, 4).reshape(
            b, kvh // 2, n, 2 * hd), 2 * group, axis=1)
    if sp is None:
        maps = flash_attention(q, k_all, v_all, True, dec.sm_scale, window)
    else:
        maps = _attend_cache(q, k_all, v_all, sp, dec.sm_scale, window)
    with jax.named_scope("diff_attention_combine"):
        f32 = jnp.float32
        lam_init = diff_lambda_init(index)
        lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
               - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
               + lam_init).astype(f32)
        maps = maps.reshape(b, h // 2, 2, L, 2 * hd).astype(f32)
        out = rms_norm(maps[:, :, 0] - lam * maps[:, :, 1],
                       layer["sub_norm"], dec.norm_eps) * (1.0 - lam_init)
        return out.astype(q.dtype).transpose(0, 2, 1, 3).reshape(
            b, L, h * hd)


def _kv_state(dec: Decoder, layer, batch, max_len, dtype):
    """An attention layer's kv heads up to `max_len`, not their copies
    across a group."""
    shape = (batch, dec.n_kv_heads, max_len, dec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _sparse_state(dec: Decoder, layer, batch, max_len, dtype):
    """ATTENTION's state and the indexer's rotated keys up to `max_len`,
    one head of them: what a new query is scored against."""
    return {**_kv_state(dec, layer, batch, max_len, dtype),
            "k_index": jnp.zeros(
                (batch, max_len, layer["index_wk"].shape[1]), dtype)}


def _latent_state(dec: Decoder, layer, batch, max_len, dtype):
    """A latent-attention layer's normed latents and rotated shared keys up
    to `max_len`: no head has a key or a value of its own here."""
    latent, r, _, _ = _latent_sizes(layer, dec.n_heads)
    return {"latent": jnp.zeros((batch, max_len, latent), dtype),
            "k_rope": jnp.zeros((batch, max_len, r), dtype)}


def _mamba2_state(dec: Decoder, layer, batch, max_len, dtype):
    conv_dim, taps = layer["conv_w"].shape      # inner + 2 groups x state
    return {"conv": jnp.zeros((batch, taps - 1, conv_dim), dtype),
            "ssm": jnp.zeros((batch, dec.ssm_heads, dec.ssm_head_dim,
                              dec.ssm_state), jnp.float32)}


def _mamba1_state(dec: Decoder, layer, batch, max_len, dtype):
    inner, taps = layer["conv_w"].shape
    return {"conv": jnp.zeros((batch, taps - 1, inner), dtype),
            "ssm": jnp.zeros((batch, inner, layer["A_log"].shape[1]),
                             jnp.float32)}


def _delta_state(dec: Decoder, layer, batch, max_len, dtype):
    H, K, V = _delta_sizes(layer)
    taps = layer["conv_w"].shape[1]
    return {"conv": jnp.zeros((batch, taps - 1, H * (2 * K + V)), dtype),
            "delta": jnp.zeros((batch, H, K, V), jnp.float32)}


def _kda_state(dec: Decoder, layer, batch, max_len, dtype):
    H, K, V = _kda_sizes(layer)
    taps = layer["conv_w"].shape[1]
    return {"conv": jnp.zeros((batch, taps - 1, H * (2 * K + V)), dtype),
            "kda": jnp.zeros((batch, H, K, V), jnp.float32)}


def _short_conv_state(dec: Decoder, layer, batch, max_len, dtype):
    taps, d = layer["conv_taps"].shape
    return {"conv": jnp.zeros((batch, taps - 1, d), dtype)}


def _no_state(dec: Decoder, layer, batch, max_len, dtype):
    """A layer that reads what another made, or mixes no sequence."""
    return {}


def _attention(x, layer, dec, cache, start_pos, shared, index, window):
    return (*attention(x, layer, dec, cache, start_pos), shared)


def _windowed_attention(x, layer, dec, cache, start_pos, shared, index,
                        window):
    return (*attention(x, layer, dec, cache, start_pos, window), shared)


def _attention_nope(x, layer, dec, cache, start_pos, shared, index, window):
    return (*attention(x, layer, dec._replace(rope_base=None), cache,
                       start_pos), shared)


def _latent_attention(x, layer, dec, cache, start_pos, shared, index, window):
    return (*latent_attention(x, layer, dec, cache, start_pos), shared)


def _sparse_attention(x, layer, dec, cache, start_pos, shared, index, window):
    y, new_cache, stats = sparse_attention(x, layer, dec, cache, start_pos)
    return y, new_cache, shared, stats


def _mamba2(x, layer, dec, cache, start_pos, shared, index, window):
    return (*mamba2(x, layer, dec, cache, start_pos), shared)


def _gated_delta(x, layer, dec, cache, start_pos, shared, index, window):
    return (*gated_delta(x, layer, dec, cache, start_pos), shared)


def _kda(x, layer, dec, cache, start_pos, shared, index, window):
    y, new_cache, stats = kda(x, layer, dec, cache, start_pos)
    return y, new_cache, shared, stats


def _short_conv(x, layer, dec, cache, start_pos, shared, index, window):
    return (*short_conv(x, layer, dec, cache, start_pos), shared)


def _mamba1(x, layer, dec, cache, start_pos, shared, index, window):
    y, new_cache, m = mamba1(x, layer, dec, cache, start_pos)
    return y, new_cache, shared._replace(m=m)


def _gmu(x, layer, dec, cache, start_pos, shared, index, window):
    return gmu(x, layer, dec, shared.m), cache, shared


class Mixer(NamedTuple):
    """A row of MIXERS: a kind of layer's sequence mixer, its state, and
    the branches of its block."""
    # (x, layer, dec, cache, start_pos, shared, index, window)
    #     -> (y, new cache, shared[, stats]); None: the block has no
    # sequence branch. `stats`, where a mixer hands any (a loss term of its
    # own, counters), leave the stack beside the channel mixer's, in the
    # layer's one entry of `decoder_hidden`'s list.
    apply: Optional[Callable]
    # (dec, layer, batch, max_len, dtype) -> {name: [batch, ...]}
    state: Callable
    windowed: bool = False      # `window` is dec.window; None elsewhere
    hands_on_kv: bool = False   # its keys and values become Shared.k, v
    reads_index: bool = False   # `index` is its place; 0 elsewhere
    channel: bool = True        # the block has its channel mixer's branch


def _differential(own, windowed=False, hands_on=False) -> Mixer:
    def apply(x, layer, dec, cache, start_pos, shared, index, window):
        return diff_attention(x, layer, dec, cache, start_pos, shared, index,
                              window, own, hands_on)
    return Mixer(apply, _kv_state if own else _no_state, windowed, hands_on,
                 reads_index=True)


# kind -> its row: the one place that says what a layer of a kind runs and
# what it keeps. The layer loop (`decoder_hidden`, `_block`) and
# `empty_cache` both go through it; a new sequence mixer adds its function
# above and its row here. The table's one rule: every leaf of a `state` has
# the batch first (models.generate.make_continuous_fns cuts axis 0 of every
# leaf).
#
# What is frozen. The benchmark reaches into this module on the chip, where
# no tier-1 test runs but tests/test_mixer_table.py's stand-in
# (chipbench/families/granite_hybrid.py, sambay.py, olmo_hybrid.py:
# `hold_kernels` and `planted`). Seven attributes of this module keep their
# names and positional signatures:
#     ssm_scan(x, dt, a, B, C, D, chunk, init)
#     selective_scan(x, dt, A, B, C, D, init)
#     flash_attention
#     gated_delta_rule(q, k, v, g, beta, chunk, init)
#     _unit_heads(t, heads, scale, eps)
#     gmu(x, layer, dec, m)
#     differential_maps(q, k, v, layer, dec, index, window)
# and, chipbench/families/nemotron_h.py's, lfm2_moe.py's and xing4.py's,
# seven more:
#     gated_rms_norm(y, gate, weight, eps, groups)
#     held_moe_layer(x, router_w, router_bias, w_up, w_down, shared_up,
#                    shared_down, *, experts_per_token, first, routed_scale,
#                    bias_rounds[, gated, weight_eps])
#     gated_short_conv(bcx, weight, tail)
#     head_rms_norm(t, weight, eps)
#     latent_attention(x, layer, dec[, cache, start_pos])
#     hyper_connection(streams, hc, hyper)
#     _streams_read(x, hc, hyper) -> (read, write, counters)
#         (`_block` hands it a fourth for a channel branch, the name
#         `write`'s operand keeps under remat; a call with three names
#         nothing)
# and, chipbench/families/glm4_moe_lite.py's, one more, which it swaps too
# and, with `_block_of(dec, *_block_keys(dec, layers)[i])`, calls:
#     prediction_module(h, embedded, module, block, eps) -> (rows, stats)
# beside `_next_targets(targets)` and `joint_loss(x, x_next, head, targets,
# weight)` on models/glm4_moe_lite.py, found through that module's names.
# The first six and these but the last the benchmark also SWAPS on the
# module (`setattr(decoder, name, faulty)`) and then traces the program, so
# the program must find
# them through the module's global name at the time of the call. A row
# that held the function object `gmu` itself, bound at import, would run
# the real one under a planted fault, and the fault would read as harmless
# with no error. So every `apply` here is a function whose BODY calls
# `gmu(...)`, `mamba2(...)` and the rest by name, and the mixers call the
# kernels the same way. Frozen besides: every parameter key, every
# `*_init`'s `jax.random.split` layout, every `jax.named_scope` and
# `checkpoint_name`, KEPT_UNDER_REMAT, and `_block`'s name (it is what
# `jax.checkpoint` wraps).
MIXERS: Dict[str, Mixer] = {
    ATTENTION: Mixer(_attention, _kv_state),
    MAMBA2: Mixer(_mamba2, _mamba2_state),
    MAMBA1: Mixer(_mamba1, _mamba1_state),
    GATED_DELTA: Mixer(_gated_delta, _delta_state),
    GMU: Mixer(_gmu, _no_state),
    DIFF_WINDOWED: _differential(own=True, windowed=True),
    DIFF_FULL: _differential(own=True, hands_on=True),
    DIFF_CROSS: _differential(own=False),
    ATTENTION_ONLY: Mixer(_attention, _kv_state, channel=False),
    MAMBA2_ONLY: Mixer(_mamba2, _mamba2_state, channel=False),
    EXPERTS: Mixer(None, _no_state),
    SHORT_CONV: Mixer(_short_conv, _short_conv_state),
    LATENT_ATTENTION: Mixer(_latent_attention, _latent_state),
    SPARSE_ATTENTION: Mixer(_sparse_attention, _sparse_state),
    KDA: Mixer(_kda, _kda_state),
    WINDOWED_ATTENTION: Mixer(_windowed_attention, _kv_state, windowed=True),
    ATTENTION_NOPE: Mixer(_attention_nope, _kv_state),
}


def _rows(dec: Decoder, layers) -> List[Mixer]:
    """The row of each of `layers`, by `dec.kinds`."""
    if len(dec.kinds) != len(layers) or set(dec.kinds) - set(MIXERS):
        raise ValueError(f"Decoder.kinds {dec.kinds}: one of {sorted(MIXERS)} "
                         f"for each of the {len(layers)} layers, no other")
    if len(dec.mlp) != len(layers):
        raise ValueError(f"Decoder.mlp names {len(dec.mlp)} channel mixers: "
                         f"one for each of the {len(layers)} layers")
    return [MIXERS[kind] for kind in dec.kinds]


def empty_cache(dec: Decoder, layers, batch, max_len, dtype) -> List[Dict]:
    """The state of each of `layers` (a model's `params["layers"]` or
    their shapes), by the row of its kind."""
    return [row.state(dec, layer, batch, max_len, dtype)
            for row, layer in zip(_rows(dec, layers), layers)]


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
# A held share of two-matrix experts (parallel/moe.py `_held_experts`)
# keeps the router's scores `moe_probs` alone. Its buffers hold a balanced
# share's rows and an eighth, and its rule walks them in as many passes as
# the routing needs, which no trace knows: so the rule's residuals are its
# inputs, it makes a pass's dispatched rows and one pre-activation again
# itself (a gather of 13,824 rows and a grouped matmul over the held ones
# at 16,384 tokens of Nemotron-3-Nano), and the block's second forward of
# the experts is dead code. One pass's rows kept would be 74 + 51 MB a
# layer; keeping them where one pass is all is not done here (XLA gives
# the cell's step 12.46 GB with none kept; PERF.md section 7, PR 46).
# Of a Mamba-2 layer, by the same rule: what the scan kernel made and a
# backward pass reads, the state each chunk left (its backward kernel's)
# and y (the gated norm's, after it) (ops/ssm_scan.py; 0.13 GB each a
# layer at 16,384 tokens of granite-4.0-h-micro). Its input projection,
# convolution, gated norm and output projection are made again.
# Of a Mamba-1 layer the same two: the state each chunk of 64 tokens left
# and the scan's output m (ops/selective_scan.py; 0.08 and 0.17 GB a layer
# at 16,384 tokens of Phi-4-mini-flash-reasoning), which is a block output
# besides where a gated memory unit reads it.
# Of a gated-delta-rule layer three: the state ENTERING each chunk of 64
# tokens, float32 [chunks, heads, K, V], from which the backward kernel
# makes the chunk's W, U and V' again (0.57 GB a layer at 16,384 tokens of
# Olmo-Hybrid-7B, 30 heads of 96 x 192); each head's and chunk's T - I as
# it enters them, in the model's dtype (0.06 GB: ten float32 products a
# pair of heads and chunk the backward kernel does not run again); and the
# rule's output o (the gated norm's, after it; 0.19 GB). Its projections,
# convolution, L2 norms, beta, g, gated norm and output projection are
# made again; no other [C, C] tile and no V' ever reaches HBM.
# Of a gated short convolution nothing: its kernels' residuals are their
# inputs, the [T, 3d] projection, which a rematerialised block makes again
# with the forward kernel's y (ops/short_conv.py).
# These seventeen are what EVERY rematerialised block keeps, on any device
# and at any size. What a block may keep besides (a mixer's input
# projection, an MLP's gate and up, a held share's routing) is in a second
# table below, KEPT_WHERE_IT_FITS, and `remat_plan` hands those names out
# layer by layer into the memory a step leaves free: they are not added
# here, because a name added here is kept by every layer of every model,
# and what fits one cell overflows the next (the Mamba-2 input projection:
# 3.6 GiB free in Nemotron-3-Nano's cell, 15.56 GiB of 15.75 taken in
# granite-4.0-h-micro's with all nine kept).
KEPT_UNDER_REMAT = (
    "attention_qkv", "flash_attention_q", "flash_attention_k",
    "flash_attention_v", "flash_attention_out", "flash_attention_lse",
    "moe_probs", "moe_xs", "moe_gate", "moe_up",
    "ssm_scan_y", "ssm_scan_states",
    "selective_scan_m", "selective_scan_states",
    "gated_delta_o", "gated_delta_states", "gated_delta_T")
keep_kernel_outputs = jax.checkpoint_policies.save_only_these_names(
    *KEPT_UNDER_REMAT)

# A kind whose block keeps other names than those seventeen. A latent
# layer's keys and values are one product away from latent + rope widths a
# token (`_from_latent`), so its block keeps the normed latent and the
# rotated shared key in the place of per-head K and V: 19 MB a layer against
# 336 at 16,384 tokens of Xing4.0's 32 heads of 192 | 128, for a [T, 512] x
# [512, 8192] product and K's assembly made again. (Per-head K and V are no
# candidates of KEPT_WHERE_IT_FITS: nothing has measured what keeping them
# saves.)
# Nor does it keep q among what EVERY such block keeps: q is two products
# and a rotary away from the block's input (through the 768-wide query
# latent: 0.13 TFLOP a layer at GLM-4.7-Flash's 20 heads of 256, under a
# millisecond of a v5e), and 168 MB a layer there, where six blocks and two
# losses beside 9.3 GB of state leave no room: q is the first name of
# KEPT_WHERE_IT_FITS instead, kept layer by layer where the step has the
# room (Xing4.0's cell: in all five layers, as before it was a candidate).
# A sparse-attention layer keeps, beside those seventeen, what its
# selection made: the selection itself (`sparse_selected`, [T, T] int8: 268
# MB a layer at 16,384 tokens, where scoring every causal pair again and
# selecting again is a [T, T] float32 and 32 counts of it), and what its
# indexer's rule left for the backward pass, which is that pass whole at a
# cotangent of one (`sparse_index_grads`, ops/sparse_index.py: 34 MB), so a
# rematerialised block runs neither indexer kernel and no target pass
# again. `sparse_index_k`, the rotated index key, is named for a plan that
# would make the scores again from it and is kept by none. Nor does the
# block keep q, k and v among what EVERY such block keeps: they are two
# products, two head norms and a rotary away from the block's input (0.3
# TFLOP a layer at 32 | 4 heads of 128), and k and v as the kernels take
# them are copies across a group of eight, 134 MB each a layer at 16,384
# tokens where six selections already hold 1.6 GB. q stays a candidate of
# KEPT_WHERE_IT_FITS, as a latent layer's is, and what q, k and v are made
# from is one of this kind's own, with the branch's output (FITS_BY_KIND
# below: the two projections' outputs and k at kv-head width, 184 MB a
# layer there for the copies' 268): where the step has the room a block
# makes only k's and v's copies again, a broadcast each, the head norms'
# inverse roots, and `ln1` for the projections' weight gradients.
# A windowed or position-free attention layer of a stack that mixes the two
# (Trinity's: 48 query heads over 8 key-value heads of 128, a gate a
# channel) keeps the kernel's output and lse and no q, k or v among what
# EVERY such block keeps: as the kernels take them k and v are copies
# across a group of six, 101 MB each a layer at 8,192 tokens beside q's
# 101, where the step's state is three quarters of the chip (0.40 GB a
# block with them, 2.34 GB over five blocks against 0.83 without: the plan
# then has 0.6 GB less than the chip; PERF.md section 4). What they are made
# from is among the kind's candidates below.
_LESS_QKV = tuple(
    name for name in KEPT_UNDER_REMAT
    if name not in ("flash_attention_k", "flash_attention_v",
                    "flash_attention_q"))
KEPT_BY_KIND: Dict[str, Tuple[str, ...]] = {
    LATENT_ATTENTION: _LESS_QKV + ("mla_latent", "mla_k_rope"),
    SPARSE_ATTENTION: _LESS_QKV + ("sparse_selected", "sparse_index_grads"),
    WINDOWED_ATTENTION: _LESS_QKV, ATTENTION_NOPE: _LESS_QKV,
    # A KDA layer keeps what a gated-delta-rule layer does, under its own
    # kernels' names (ops/kda.py): the state ENTERING each chunk of 64
    # tokens (the model's dtype [chunks, heads, K, V]: 268 MB a layer at
    # 16,384 tokens of 32 heads of 128 x 128), each head's and chunk's T - I
    # (67 MB) and the rule's output o (134 MB). Its projections, convolution,
    # L2 norms, beta, the float32 log-decay g [T, heads * K] (268 MB a
    # layer there, where gated_delta's is a [T, 30]: a candidate below),
    # head norm, gate and output projection are made again.
    KDA: KEPT_UNDER_REMAT + ("kda_o", "kda_states", "kda_T")}


def _kept(kind: str) -> Tuple[str, ...]:
    """The names every rematerialised block of `kind` keeps."""
    return KEPT_BY_KIND.get(kind, KEPT_UNDER_REMAT)


# What a rematerialised block keeps BESIDES, layer by layer, where the step
# has the room (`remat_plan`): values the backward pass reads that no kernel
# made, cheap to keep for what making them again costs. A name here is
# kept by as many layers as the shapes say fit, in the order of cost saved
# a byte kept, and by none where no capacity is known (the CPU: the base
# set's program).
# name -> what making a value of that name again costs, as a function
# (value's shape and dtype, the block's width d) -> flops, or bytes moved
# counted as the flops the chip does meanwhile:
#     ssm_in_proj     Mamba-2's z | x B C | dt, Mamba-1's x | z
#     ssm_gated       Mamba-2's gated norm's output, the output projection's
#                     input: three passes of its bytes (the scan's output
#                     and the gate read, the value written)
#     gated_delta_in  the delta rule's q | k | v and its gate
#     short_conv_in   the short convolution's B | C | x
#     mlp_gate_up     a dense SwiGLU's gate and up, fused or apart
#     moe_shared_up   a shared expert's up projection, float32
#     moe_choice      a held share's routing: the top-k's experts and the two
#                     sorts' orders, integers (parallel/moe.py): two sorts
#                     for under a megabyte a layer, before everything else
#     flash_attention_q
#                     a latent layer's q, which its kind's base set leaves out
#                     (KEPT_BY_KIND; any other kind keeps it always and it is
#                     no candidate there): before everything else too.
#                     By the clock it is the cheapest byte here to make
#                     again (below), but a step of Xing4.0's cell that makes
#                     q again in every layer is an executable 3.4 times the
#                     size (653 MB serialized against 190, 118 against 35 in
#                     the persistent cache, compiled for a v5e: q kept or not
#                     is all that moves it), which the compile cache of the
#                     machines the cell runs on no longer holds beside the
#                     cell's other programs: every run then compiles all of
#                     them (`setup_s` 126 -> 540 s). Understood since PR
#                     58: it was not q. XLA:TPU shares the code of the
#                     layers only where the step would not fit the chip
#                     otherwise, and the plans that made q again were the
#                     ones that left it room; the step asks for shared
#                     code now (models/_training.py `_SHARED_CODE`), so a
#                     plan no longer moves the executable's size. q keeps
#                     its place until a plan by the clock is measured
#                     again (PERF.md section 7 (37))
#     hc_channel_out  a hyper-connected block's channel branch as `write` is
#                     handed it (`_streams_read`): what closes the branch, a
#                     dense MLP's down projection from some d's width, or a
#                     held share's down stage, the combine's k gathers of T
#                     rows and the shared expert's down projection. Kept, the
#                     experts' forward is dead code in the block's second
#                     run, as it is under the add (the rule's residuals are
#                     its inputs)
# The costs are in the order the chip gave them, name by name, in Xing4.0's
# cell (one call, one seed, `scope_profile.py`'s busy ms a step under forced
# tables; PERF.md section 6, PR 56), ms saved a GB kept: hc_channel_out 68.9
# (8.1 ms a layer for 117 MB: 3.8 matmuls an element), mlp_gate_up 20.9,
# moe_shared_up 19.1 (1.15 and 1.05: a matmul each), flash_attention_q 12.3
# (0.67 of one; 9.8 in GLM-4.7-Flash's, PR 55). The mixer branch's output
# (its output projection alone: 20.0, 1.10) has no name yet: at a matmul an
# element it would take, by the order of layers, the room that cell's
# shared up projections hold, and without those XLA's total for the step
# is 15.90 GB where with them it is 15.57 (PERF.md section 7).
# (only the candidates' order reads the costs, so one chip's ratio of the
# two serves every chip whose matmuls outrun its memory)
_FLOPS_A_BYTE = 240         # a v5e's 197 TFLOP/s over its 819 GB/s


def _a_matmul(value, d: int) -> float:
    """A row of the block's d-wide input times a column, an element."""
    return 2.0 * d * value.size


def _passes(count: float) -> Callable:
    """So many passes of the value's bytes over HBM."""
    def cost(value, d: int) -> float:
        return count * value.size * value.dtype.itemsize * _FLOPS_A_BYTE
    return cost


def _matmuls(count: float) -> Callable:
    """So many of `_a_matmul`."""
    def cost(value, d: int) -> float:
        return count * _a_matmul(value, d)
    return cost


def _first(value, d: int) -> float:
    return math.inf


KEPT_WHERE_IT_FITS: Dict[str, Callable] = {
    "ssm_in_proj": _a_matmul, "ssm_gated": _passes(3),
    "gated_delta_in": _a_matmul, "short_conv_in": _a_matmul,
    "mlp_gate_up": _a_matmul, "moe_shared_up": _a_matmul,
    "moe_choice": _first, "flash_attention_q": _first,
    "hc_channel_out": _matmuls(4)}

# Candidates of one kind's blocks alone, beside those: what a sparse block's
# q, k and v are made from where the model holds `wq` and `wkv` apart
# (`_qkv_heads`' unfused arm), and the branch's output. The head norms are
# autodiff's and their backward reads their INPUT, so with q alone kept a
# block's second forward still ran both projections, both norms in float32
# and k's rotary, and the output projection for the channel branch's input
# (5.9 ms a layer at 16,384 tokens of 32 | 4 heads of 128: PERF.md section
# 6, PR 62):
#     attention_q_proj   y wq  [T, h hd]      134 MB a layer there
#     attention_kv_proj  y wkv [T, 2 kvh hd]  34 MB: k before its norm, and v
#     attention_k_heads  k normed and rotated, at kv-head width, 17 MB: with
#                        q, a norm, a rotary and their float32 passes for it
#     sparse_attention_out
#                        the kernel's output times wo [T, d], 67 MB: a
#                        product from h hd = 2 d wide, two matmuls an element
# With the four and q kept a second forward makes `ln1`, the norms' inverse
# roots, v's transpose and k's and v's copies across their groups, which are
# a broadcast away from 17 MB each and 134 kept.
# By kind and not in the table above on purpose: `attention` blocks of four
# other cells run the same arm, their plans are full, and a candidate at a
# matmul's rate would reshuffle what they keep with nothing measured to say
# it should.
# A KDA block's: its q | k | v projection (`kda_in`, a matmul an element)
# and the float32 log-decay `kda_g`, a matmul an element too but four bytes
# wide, so half the saving a byte: after every two-byte projection.
# A windowed or position-free block's: q (KEPT_WHERE_IT_FITS's
# `flash_attention_q`, which this kind's base set leaves out) and k at
# kv-head width, where the kernels take six copies of it, before everything
# else, as a latent and a sparse block's; then what q, k and v are made from
# (the sparse kind's two projections) and the gate's projection [T, h hd], a
# matmul an element each. By the clock a projection saves four times what q
# does a byte (with its projection kept q is a head norm and a rotary away),
# but the plan that takes the projections first (q and k at three passes of
# their bytes) reads 16.54 GB by XLA's total for Trinity's step compiled for
# a v5e, over the chip, where this order reads 15.17 for the same 0.82 GB
# kept: XLA holds more than a kept projection's bytes, and `_reserve` has no
# term for it yet (PERF.md section 7).
_GATED_GQA_FITS = {"attention_q_proj": _a_matmul,
                   "attention_kv_proj": _a_matmul,
                   "attention_gate_proj": _a_matmul,
                   "attention_k_heads": _first}
FITS_BY_KIND: Dict[str, Dict[str, Callable]] = {
    SPARSE_ATTENTION: {"attention_q_proj": _a_matmul,
                       "attention_kv_proj": _a_matmul,
                       "attention_k_heads": _first,
                       "sparse_attention_out": _matmuls(2)},
    KDA: {"kda_in": _a_matmul, "kda_g": _a_matmul},
    WINDOWED_ATTENTION: _GATED_GQA_FITS, ATTENTION_NOPE: _GATED_GQA_FITS}

def _fits(kind: str) -> Dict[str, Callable]:
    """name -> cost of everything a block of `kind` may keep besides."""
    return {**KEPT_WHERE_IT_FITS, **FITS_BY_KIND.get(kind, {})}


class RematPlan(NamedTuple):
    """What each rematerialised layer keeps beyond KEPT_UNDER_REMAT, and
    the account it was decided by, in bytes a chip."""
    extras: Tuple[Tuple[str, ...], ...]    # a layer's further names, sorted
    kept_extra_bytes: int                  # what they hold, all layers
    layers_extended: int                   # layers with any, of len(extras)
    base_bytes: int            # what the base set keeps: a layer's input and
    #                            the values of KEPT_UNDER_REMAT's names
    reserve_bytes: int         # one block's backward and the loss (`_reserve`)
    state_bytes: int           # parameters, optimizer state, gradients
    capacity: Optional[int]    # the chip's memory; None: not known
    bytes_left: int            # of capacity - state - base - reserve; 0 with
    #                            no capacity


def _chip_capacity(mesh) -> Optional[int]:
    """The memory of one of the devices the step runs on, by the device: a
    constant of the chip's kind, not of what is allocated now, so every
    run of a job traces the same step. None where the device keeps no
    account (the CPU)."""
    device = jax.local_devices()[0] if mesh is None else mesh.devices.flat[0]
    stats = device.memory_stats()
    return stats.get("bytes_limit") if stats else None


def _rows_and_width(x) -> Tuple[int, int]:
    """(batch x L, d) of a stack's x: [batch, L, d], or so many of them
    where the stack carries several streams."""
    stream = jax.tree.leaves(x)[0]
    return math.prod(stream.shape[:-1]), stream.shape[-1]


def _nbytes(values) -> int:
    return sum(math.prod(v.shape) * jnp.dtype(v.dtype).itemsize
               for v in jax.tree.leaves(values))


def _block_account(block: Callable, x, layer, shared,
                   kept: Tuple[str, ...] = KEPT_UNDER_REMAT,
                   fits: Dict[str, Callable] = KEPT_WHERE_IT_FITS):
    """One abstract linearisation of `block` under a policy that keeps
    `kept`, its kind's base set, and the names of `fits`, its kind's
    candidates (`_fits`) -> (the
    block's x and `Shared` as it returns them,
    the bytes it keeps of the base set, ((name, bytes, cost), ...) of each
    value with a name of `fits`). Shapes in, shapes out:
    nothing is computed and no kernel is lowered."""
    policy = jax.checkpoint_policies.save_only_these_names(*kept, *fits)

    def linearized(x, layer, shared):
        out, pushforward = jax.linearize(
            jax.checkpoint(lambda *a: block(a[0], a[1], None, None, a[2]),
                           policy=policy), x, layer, shared)
        return (out[0], out[3]), pushforward

    closed, ((x_out, shared_out), pushforward) = jax.make_jaxpr(
        linearized, return_shape=True)(x, layer, shared)
    jaxpr = closed.jaxpr
    # The pushforward's leaves are what the forward pass left for the
    # backward: the jaxpr's last outputs. Those that are its own inputs
    # (x, the weights, `Shared`) are not the block's to count.
    residuals = jaxpr.outvars[len(jaxpr.outvars)
                              - len(jax.tree.leaves(pushforward)):]
    given = {id(v) for v in jaxpr.invars}
    held = _nbytes({id(v): v.aval for v in residuals
                    if isinstance(v, Var) and id(v) not in given})
    # (a name of both tables is the base set's: no candidate)
    extras = tuple(
        (eqn.params["name"], _nbytes(eqn.outvars[0].aval),
         fits[eqn.params["name"]](eqn.outvars[0].aval,
                                  _rows_and_width(x)[1]))
        for eqn in jaxpr.eqns if eqn.primitive.name == "name"
        and eqn.params["name"] in fits
        and eqn.params["name"] not in kept)
    base = _nbytes(x) + held - sum(size for _, size, _ in extras)
    return x_out, shared_out, base, extras


def _scaled(t, scale: float):
    return t if scale == 1.0 else t * scale


# The scope of a kind's sequence-mixer branch, by the key of MIXERS: what
# util/profiling.by_scope files a block's device time under. Two kinds
# that run one mixer share its name.
MIXER_SCOPES: Dict[str, str] = {
    kind: kind.removesuffix("_only").removesuffix("_nope") + "_mixer"
    for kind in MIXERS if MIXERS[kind].apply is not None}


@jax.custom_vjp
def _stream_products(x, phi):
    """x [b, L, d] times phi [d, k], accumulated and returned in float32.
    The rule is written out for the cotangent of x alone: derived, it is a
    float32 [b, L, d] value that is rounded to x's dtype next; here the
    k-wide cotangent is rounded first and the product comes out in x's
    dtype (0.94 GB less alive at 16,384 tokens of four 3,584-wide streams,
    by XLA's account of the step compiled for a v5e)."""
    return jnp.einsum("bsd,dk->bsk", x, phi,
                      preferred_element_type=jnp.float32)


def _stream_products_fwd(x, phi):
    return _stream_products(x, phi), (x, phi)


def _stream_products_bwd(saved, g):
    x, phi = saved
    g = g.astype(x.dtype)
    d_phi = jnp.einsum("bsd,bsk->dk", x, g,
                       preferred_element_type=jnp.float32)
    return jnp.einsum("bsk,dk->bsd", g, phi), d_phi.astype(phi.dtype)


_stream_products.defvjp(_stream_products_fwd, _stream_products_bwd)


def hyper_connection(streams, hc, hyper: HyperConnections):
    """The coefficients that join one branch to the n residual streams
    (`streams`: n arrays [b, L, d]) by manifold-constrained
    hyper-connections (mHC, DeepSeek 2025, over Hyper-Connections,
    arXiv:2409.19606), a token each, all in float32: with x the token's
    streams as one vector [n d], normed with no weight (phi absorbs one),
    [p | q | r] = x^ phi (`hc["phi"]` [n d, 2 n + n^2]), `hc["alpha"]` [3]
    the three gains and `hc["b"]` [2 n + n^2] the static part,

        H_pre  = sigmoid(alpha_0 p + b_pre)              [b, L, n]
        H_post = 2 sigmoid(alpha_1 q + b_post)           [b, L, n]
        H_res  = sinkhorn(exp(clamp(alpha_2 r + b_res))) [b, L, n, n]

    the last `hyper.sinkhorn_iters` rounds of columns then rows each over
    their sum + `hyper.eps`, which leaves rows summing to 1 exactly and
    columns nearly: a doubly stochastic mix of the streams, whose products
    down a stack stay doubly stochastic. The branch reads sum_i H_pre[i]
    X[i] and the block returns H_res X + H_post (x) branch (`_streams_read`).
    The norm's factor is a scalar a token, so it multiplies the 2 n + n^2
    products and no normed copy of the streams is made."""
    n, (b, L, d), f32 = len(streams), streams[0].shape, jnp.float32
    mean_sq = sum(jnp.sum(jnp.square(x.astype(f32)), axis=-1, keepdims=True)
                  for x in streams) / (n * d)
    phi = hc["phi"].reshape(n, d, -1)
    raw = sum(_stream_products(x, phi[i]) for i, x in enumerate(streams)) \
        * jax.lax.rsqrt(mean_sq + hyper.eps)
    alpha, bias = hc["alpha"].astype(f32), hc["b"].astype(f32)
    p, q, r = jnp.split(raw, [n, 2 * n], axis=-1)
    b_pre, b_post, b_res = jnp.split(bias, [n, 2 * n])
    h_pre = jax.nn.sigmoid(alpha[0] * p + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * q + b_post)
    m = jnp.exp(jnp.clip((alpha[2] * r + b_res).reshape(b, L, n, n),
                         *hyper.clamp))
    for _ in range(hyper.sinkhorn_iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hyper.eps)   # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hyper.eps)   # rows
    return h_pre, h_post, m


def _streams_read(x, hc, hyper: Optional[HyperConnections],
                  out_name: Optional[str] = None):
    """What a branch reads of the block's x, how its output is joined to
    it, and what the join counts. With no hyper-connection weights `hc`: x
    itself, the residual add, nothing. With them, x is the streams, n
    arrays [b, L, d]: the branch reads their mix by H_pre [b, L, d],
    `write(y)` is H_res X + H_post (x) y, n arrays again, and the counters
    are the largest off-diagonal entry of H_res and the largest |column
    sum - 1| (the streams still mix; the matrix is still on its manifold).
    The mixes accumulate in float32 and return the streams' dtype. The
    value `write` is handed carries `out_name` where one is given
    (`_block`: a channel branch's is `hc_channel_out`, a candidate of
    KEPT_WHERE_IT_FITS): H_post's gradient is a row dot of the streams'
    cotangent with it, so a rematerialised block that does not keep it runs
    the whole branch again to have it.
    The streams are apart, not one [b, L, n, d] value, so that every mix is
    multiply-adds of [b, L, d] values by a column a token: XLA for the v5e
    copies slices of a stream axis out in float32 and pads them back in
    the backward pass (3.7 GB more alive a step at 16,384 tokens of four
    3,584-wide streams, compiled for the chip)."""
    if hc is None:
        return x, lambda y: x + y, None
    n, f32, dtype = len(x), jnp.float32, x[0].dtype
    with jax.named_scope("hc_coefficients"):
        h_pre, h_post, h_res = hyper_connection(x, hc, hyper)
        off_diagonal = jnp.max(h_res * (1.0 - jnp.eye(n, dtype=f32)))
        column_error = jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1.0))
    with jax.named_scope("hc_read"):
        u = sum(h_pre[..., i, None] * x[i].astype(f32)
                for i in range(n)).astype(dtype)

    def write(y):
        if out_name is not None:
            y = checkpoint_name(y, out_name)
        with jax.named_scope("hc_write"):
            return tuple(
                (h_post[..., i, None] * y.astype(f32)
                 + sum(h_res[..., i, j, None] * x[j].astype(f32)
                       for j in range(n))).astype(dtype)
                for i in range(n))

    return u, write, (off_diagonal, column_error)


def _with_hc_counters(stats, counted):
    """A block's `stats` with its hyper-connected branches' counters beside
    the channel mixer's own: `hc_res_offdiag_max`, `hc_res_col_err_max`,
    each a tuple of one scalar a branch in the block's order (as the
    branches' scopes made them: no operation stands outside a branch)."""
    counted = [c for c in counted if c is not None]
    if not counted:
        return stats
    off_diagonal, column_error = zip(*counted)
    return {**(stats or {}), "hc_res_offdiag_max": off_diagonal,
            "hc_res_col_err_max": column_error}


def _block(x, layer, cache, start_pos, shared: Shared = Shared(), *,
           dec: Decoder, kind: str, mlp: Optional[Callable] = None,
           index: int = 0, window=None):
    """One layer: its kind's sequence mixer and `mlp`, the layer's channel
    mixer (None where the kind's block has no such branch), each branch
    with its norms and its residual join under a scope of its own: the add,
    or where the layer holds `hc_mixer` / `hc_mlp` the hyper-connection of
    x's streams (`_streams_read`); a branch sees [b, L, d] either way."""
    eps, row = dec.norm_eps, MIXERS[kind]
    stats, new_cache = None, cache
    counted = []
    if row.apply is not None:
        with jax.named_scope(MIXER_SCOPES[kind]):
            u, write, counters = _streams_read(x, layer.get("hc_mixer"),
                                               dec.hyper)
            y, new_cache, shared, *mixer_stats = row.apply(
                u, layer, dec, cache, start_pos, shared, index, window)
            x = write(_scaled(_norm_if_held(y, layer, "post_attention", eps),
                              dec.residual_scale))
            counted.append(counters)
    if row.channel:
        with jax.named_scope("channel_mixer"):
            u, write, counters = _streams_read(x, layer.get("hc_mlp"),
                                               dec.hyper, "hc_channel_out")
            out, stats = mlp(_norm_if_held(u, layer, "ln2", eps), layer)
            out = _norm_if_held(out, layer, "post_feedforward", eps)
            x = write(_scaled(out, dec.residual_scale))
            counted.append(counters)
    if row.apply is not None and mixer_stats:
        stats = {**mixer_stats[0], **(stats or {})}
    return x, _with_hc_counters(stats, counted), new_cache, shared


def _block_keys(dec: Decoder, layers) -> List[Tuple]:
    """(kind, mlp, index, window) a layer: what its block is traced by. A
    kind that reads neither its place nor a window runs one block a
    channel mixer, traced once a shape."""
    return [(kind, mlp if row.channel else None,
             i if row.reads_index else 0,
             dec.window if row.windowed else None)
            for i, (kind, row, mlp) in enumerate(zip(
                dec.kinds, _rows(dec, layers), dec.mlp))]


def _block_of(dec: Decoder, kind: str, mlp: Optional[Callable], index: int,
              window: Optional[int]) -> Callable:
    return functools.partial(_block, dec=dec, kind=kind, mlp=mlp,
                             index=index, window=window)


# The margin `remat_plan` leaves under a chip's capacity: XLA's own
# figure for the step is to stay a GiB under it (the runtime holds some
# back, and a kept value costs up to a fifth above its bytes where XLA keeps
# the pieces it is split into beside it).
_UNDER_CAPACITY = 2 ** 30


def _backward_holds(mlp: Optional[Callable], tokens: int, layer) -> int:
    """What a channel mixer's hand-written backward rule holds that no name
    shows, by the rule's own account: a held share of experts'
    (parallel.moe.held_backward_bytes). Nothing for the others: autodiff
    derives their backward from values the account has seen."""
    mixer = getattr(mlp, "func", mlp)
    if mixer not in (held_routed_experts, held_gated_experts):
        return 0
    up = "expert_gate_up" if mixer is held_gated_experts else "expert_up"
    return held_backward_bytes(
        tokens, mlp.keywords["experts_per_token"], layer["router"].shape[-1],
        layer[up], layer["expert_down"])


def _streams_hold(x, layer) -> int:
    """What the backward pass of a hyper-connected block holds that no name
    shows: two and a half values of the streams' size (ten of one
    stream's), the streams between its two branches made again and the
    cotangents of its output and of those, less what XLA shares among them.
    A calibration, not a count: XLA's account of Xing4.0's step compiled
    for a v5e, total - state - base set - what the plan keeps, reads 3.13
    to 3.27 GB under six plans that keep every layer's `hc_channel_out`
    (3.13 under the one the cell runs: 15.57 GB in all) and 3.23 with the
    base set alone, where the named values and the held experts' rule
    account for 2.09 and these for 1.17: 3.26. It was four values while
    the channel branch's output had no name and the branch was made again
    beside five kept q: 3.61 of 3.85, PR 53. The account is not from above
    for every set of names: keeping q and every channel output but no
    shared expert's up projection reads 3.74 (XLA holds more where it
    makes that projection again), which this table's order does not give
    at a v5e's capacity; tests/test_compile_v5e_xing4.py holds the total
    of the plan it does give (PERF.md section 6, PR 56). Nothing for a
    block joined by the add."""
    hyper_connected = "hc_mixer" in layer or "hc_mlp" in layer
    return 5 * _nbytes(x) // 2 if hyper_connected else 0


def _latent_holds(kind: str, tokens: int, layer, dec: Decoder) -> int:
    """What the backward pass of a latent-attention block joined by the add
    holds that no name shows: per-head K and V made again from the kept
    latent, and the cotangents of the kernel's output, q, K and V (XLA's
    account of GLM-4.7-Flash's five layers with no module, compiled for a
    v5e with the base set kept: total - state - base set 3.08 GB, where the
    named values and the held experts' rule account for 2.13 and these six
    for 1.01; PERF.md section 6, PR 55). Nothing for a hyper-connected
    block: `_streams_hold`'s values were read with these among them."""
    if kind != LATENT_ATTENTION or "hc_mixer" in layer or "hc_mlp" in layer:
        return 0
    _, r, n, vd = _latent_sizes(layer, dec.n_heads)
    itemsize = jnp.dtype(layer["w_kvb"].dtype).itemsize
    return 3 * tokens * dec.n_heads * (n + r + vd) * itemsize


def _kda_holds(kind: str, tokens: int, layer) -> int:
    """What the backward pass of a KDA block holds that no name shows, in
    float32 [T, heads * K] values: eight of them (268 MB each at 16,384
    tokens of 32 heads of 128). Where `gated_delta`'s log-decay is a [T, 30],
    this rule's is a value a key channel: g, its running sums, the sums'
    gradient from the backward kernel and g's own are four such, and the
    convolution's hand-written backward works q | k | v, three widths of it,
    in float32 (silu's slope times the cotangent, then the taps the other
    way) beside the L2 norms' float32 passes over q and k. A calibration,
    not a count: XLA's account of Ling-3.0-flash's six-layer step compiled
    for a v5e, total - state - base set - what the plan keeps, reads 4.07 GB
    with nothing kept and 3.35 with every layer's `kda_in` kept (which the
    block's named values then count), where the block's named values and
    the held experts' rule account for 1.98: 2.09 more, and eight are 2.15
    (PERF.md section 6, PR 63)."""
    if kind != KDA:
        return 0
    H, K, _ = _kda_sizes(layer)
    return 8 * tokens * H * K * 4


def _selection_holds(kind: str, tokens: int, x) -> int:
    """What a sparse-attention block holds that no name shows, in [T, T]
    buffers a sequence: six bytes a (query, key) pair. Five are counted: at
    the step's peak, inside the last layer's target pass, the buffer
    assignment of Keye-VL-2.0's six-layer step compiled for a v5e holds the
    index scores I, float32, which their gradient is written over (four),
    and the int8 selection transposed for dK/dV (one); the sixth is for
    what a chunk of the target and the backward kernel's partial sums of
    dk_I hold beside them (0.14 + 0.13 GB there). By XLA's own account the
    six are from above under every plan tried: total - state - base set -
    what the plan keeps reads 2.04 GB with nothing kept, 2.10 with q and
    the routing's choices (the plan the cell ran until PR 62: 11.30 GB in
    all), 2.09 with k at kv-head width beside those, 1.84 with both
    projections of every layer besides and 1.44 with the branch's output
    too, every candidate of every layer (the plan the cell runs: 12.15 GB
    in all), where the block's named values come to 1.85 and these to
    1.61; the total less what is kept falls from 10.5 to 9.8, so the
    schedule that runs a layer's target and backward kernel before the
    next layer's scores holds under all five (PERF.md section 6, PR 62).
    It was sixteen while six such buffers stood at the peak (PR 60: 5.91 GB
    of 1.6 + 4.29; PR 61 took them to one). The buffers are alive in a
    block's FORWARD pass and are counted with its backward set as if they
    met: from above."""
    if kind != SPARSE_ATTENTION:
        return 0
    batch = jax.tree.leaves(x)[0].shape[0]
    return 6 * batch * (tokens // batch) ** 2


def _reserve(dec: Decoder, accounts, keys, layers, x, vocab: int, chips: int,
             losses: int = 1) -> int:
    """What a chip holds at a step's peak beside its state and what its
    blocks keep, from the shapes it holds: the loss's working set
    (ops.loss.working_set_bytes) and a block's backward pass, everything
    of the block that has a name in either table, alive at once while it
    is differentiated, and what no name shows (`unnamed`: what its channel
    mixer's own rule says it holds, `_backward_holds`, and the fitted
    `_streams_hold`, `_latent_holds`, `_selection_holds` and `_kda_holds`). On
    one chip the two do
    not meet and the largest block counts: the larger of the loss and it;
    where the step has further `losses` over the one head (a prediction
    module's), a working set each beside either: the stack's loss leaves
    its rows' and its head's gradients waiting while the module's block
    runs, scans and is differentiated, beside that block's input, the next
    tokens' rows and the module's own head gradient (XLA's account of
    GLM-4.7-Flash's step compiled for a v5e: 1.13 GB more beside the
    largest block with the module than without; a working set is 1.27).
    Where the batch
    is split over `chips` the step reduces its gradients under compute
    (models/_training.py `_ASYNC_GRADIENT_REDUCE`), and XLA moves every
    layer's weight gradients behind the last backward kernel: their
    operands, every block's working set, are alive at once, after a loss
    whose head gradient still waits for its reduce, so all of them count
    and the loss beside them. An estimate from above, so that what it
    leaves is there: PERF.md section 6, PR 51, sets it beside XLA's
    `memory_analysis()` of the six rematerialised cells' steps compiled
    for a v5e (total - state - base set) and of a step over a v5e:2x2;
    tests/test_compile_v5e_*.py hold each total."""
    tokens, d = _rows_and_width(x)
    loss = loss_working_set_bytes(tokens, d, vocab)

    def unnamed(key, layer) -> int:
        rule = _backward_holds(key[1], tokens, layer)
        fitted = _streams_hold(x, layer) \
            + _latent_holds(key[0], tokens, layer, dec) \
            + _selection_holds(key[0], tokens, x) \
            + _kda_holds(key[0], tokens, layer)
        # The cotangent of the block's output waits while the block is
        # differentiated. The two fitted terms were read off XLA's totals
        # with it among them, and a block of named values alone is counted
        # with all of them alive at once, which they are not; beside a
        # rule's own exact account nothing is slack and it shows: XLA's
        # total for LFM2's step leaves 6.154 GB beside the state, the base
        # set and what is kept, the rule and its block's names come to
        # 6.020, and a value of x's size is 0.134 (PERF.md section 6, PR 58:
        # 0.54 GB of lane padding in that block's lse had covered it).
        return rule + (fitted or (_nbytes(x) if rule else 0))

    blocks = [base + sum(size for _, size, _ in extras) + unnamed(key, layer)
              for (base, extras), key, layer in zip(accounts, keys, layers)]
    return (max(loss, *blocks) if chips == 1 else loss + sum(blocks)) \
        + (losses - 1) * loss


def remat_plan(dec: Decoder, layers, x, vocab: int, capacity: Optional[int],
               state_bytes: Optional[int], chips: int = 1,
               losses: int = 1) -> RematPlan:
    """Which names of KEPT_WHERE_IT_FITS each layer of a rematerialised
    stack keeps: a pure function of shapes, as ops.attention.attention_plan
    is of a kernel's. `layers` are a model's `params["layers"]` or their
    shapes (a prediction module's block after them where the step runs
    one: `dec` then names its kind and channel mixer last, and `losses` is
    2, the module's own beside the stack's), `x` [batch, L, d] (or the
    streams, so many of them: a block's input at its real width) the
    stack's input as ONE CHIP holds it (its shape and dtype: where the
    batch is split over chips, a chip's share of it), `vocab` the vocabulary's rows, `capacity` one chip's memory
    and `state_bytes` what the step holds there beside activations, both
    in bytes, `chips` how many the batch is split over. Every byte of the
    account is a chip's: the activations are traced at the chip's batch,
    what the batch does not split (the loss's chunk and head gradient, a
    rule's weight-sized gradients) is whole, and nothing is divided by
    `chips`, which only says how the step is scheduled (`_reserve`). Every
    layer's block is linearised abstractly once a kind and shape
    (`_block_account`) for what it keeps of the base set and for its
    candidates; a layer's values of one name go together; the candidates
    of all layers are taken in the order of cost saved a byte kept, a
    layer's before the next one's at the same rate, each that still fits
    what `capacity` leaves after the state, the base set, `_reserve` and
    those before it. More capacity never keeps fewer bytes; with no
    capacity (None, the CPU) or no state given nothing is added: the base
    set's program."""
    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    x, shared, accounts, traced = shapes(x), Shared(), [], {}
    keys = _block_keys(dec, layers)
    with a_chip_alone():
        for key, layer in zip(keys, layers):
            layer = shapes(layer)
            seen = (key, tuple(jax.tree.leaves(layer)),
                    jax.tree.structure(layer), shared)
            if seen not in traced:
                traced[seen] = _block_account(_block_of(dec, *key), x, layer,
                                              shared, _kept(key[0]),
                                              _fits(key[0]))
            x, shared, base, extras = traced[seen]
            accounts.append((base, extras))
    base = sum(base for base, _ in accounts)
    reserve = _reserve(dec, accounts, keys, layers, x, vocab, chips, losses)
    taken: List[List[str]] = [[] for _ in accounts]
    left = kept = 0
    if capacity is not None and state_bytes is not None:
        left = capacity - _UNDER_CAPACITY - state_bytes - base - reserve
        candidates = []
        for i, (_, extras) in enumerate(accounts):
            for name in sorted({name for name, _, _ in extras}):
                size = sum(s for n, s, _ in extras if n == name)
                cost = sum(c for n, _, c in extras if n == name)
                candidates.append((-cost / size, i, name, size))
        for _, i, name, size in sorted(candidates):
            if size <= left:
                taken[i].append(name)
                left, kept = left - size, kept + size
    return RematPlan(
        extras=tuple(tuple(sorted(names)) for names in taken),
        kept_extra_bytes=kept, layers_extended=sum(map(bool, taken)),
        base_bytes=base, reserve_bytes=reserve,
        state_bytes=state_bytes or 0, capacity=capacity,
        bytes_left=max(left, 0))


def _planned_extras(dec: Decoder, layers, x, vocab: int,
                    losses: int = 1) -> Tuple:
    """What each rematerialised layer keeps beyond the base set: what
    `remat_plan` adds inside a training step (ops.attention.step_memory)
    on a chip that says what it holds, nothing anywhere else. Any policy
    but `keep_kernel_outputs` is the family's own and is left alone. Where
    the step splits its batch over chips (kernel_sharding) the plan is
    asked at a chip's share of it; the state is counted whole, which is
    a chip's under data parallelism and more than a chip's where the
    parameters are split as well, so the plan then keeps less than fits."""
    state_bytes, capacity = step_memory_given()
    if dec.remat is not keep_kernel_outputs or state_bytes is None:
        return ((),) * len(layers)
    mesh, chips = None, 1
    if step_sharding() is not None:
        mesh, spec = step_sharding()
        axes = (spec[0],) if isinstance(spec[0], str) else spec[0] or ()
        chips = math.prod(mesh.shape[axis] for axis in axes)
    if capacity is None:
        capacity = _chip_capacity(mesh)
    if capacity is None:
        return ((),) * len(layers)
    a_chips = jax.tree.map(lambda t: jax.ShapeDtypeStruct(
        (-(-t.shape[0] // chips),) + t.shape[1:], t.dtype), x)
    return remat_plan(dec, layers, a_chips, vocab, capacity, state_bytes,
                      chips, losses).extras


def prediction_module(h, embedded, module: Dict, block: Callable, eps: float):
    """A multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
    section 2.2) behind a stack: `h` [b, L, d] the last block's output
    BEFORE the final norm, `embedded` [b, L, d] the main table's rows of
    the tokens one further on, each normed by a weight of its own
    (`enorm`, `hnorm`), side by side, the embedding first, times `w_eh`
    [2 d, d]; `block`, one more layer over the same positions, causal as
    any (`module["block"]` its weights: its own router, selection bias
    and, were it served, cache entry); `norm`, the module's own final
    norm -> (rows [b, L, d] that the MAIN head turns into the logits of
    the token two on, the block's `stats`). The module holds no table and
    no head: the caller looks `embedded` up in the stack's and hands the
    rows to the stack's head, so each gathers a gradient a use."""
    with jax.named_scope("mtp_embed"):
        e = _norm(embedded, module, "enorm", eps)
    with jax.named_scope("mtp_project"):
        u = jnp.einsum("bsd,de->bse", jnp.concatenate(
            [e, _norm(h, module, "hnorm", eps)], axis=-1), module["w_eh"])
    u, stats, _, _ = block(u, module["block"], None, None, Shared())
    with jax.named_scope("mtp_norm"):
        return _norm(u, module, "norm", eps), stats


def decoder_hidden(params: Dict, tokens, dec: Decoder,
                   cache: Optional[List[Dict]] = None, start_pos=None,
                   next_tokens=None):
    """tokens [b, L] -> (final-norm rows [b, L, d], the output head
    [d, vocab] (for `cross_entropy` or `decoder_logits`: a tied head under
    a data-parallel training step is `chip_views`, [chips, d, vocab]), the
    channel mixers' `stats`, a list with one entry for each layer that
    gives any, the new cache or None).
    With a `cache` (an `empty_cache`, or the last call's) the tokens sit
    at `start_pos` + [0, L) and the mixers read and write it; with none
    this is the training forward. The rows come multiplied by
    `dec.logit_scale`, so rows @ head are the model's logits.
    With `next_tokens` [b, L], the tokens one further on (a training
    forward's alone), the model's prediction module `params["mtp"]` runs
    behind the stack on the last block's output as it is before the final
    norm (`prediction_module`): `dec.kinds` and `dec.mlp` then name the
    module's block after the layers', it is rematerialised and planned as
    one of them, its `stats` come last, and the rows are a pair, (the
    stack's, the module's), for the one head."""
    # A tied table under a data-parallel training step is one view a
    # chip, so that its lookup's and its head's gradients cross the chips
    # as one sum (ops/loss.py chip_views).
    views = None if "head" in params or cache is not None \
        else chip_views(params["embed"])

    def embedded(ids):
        return lookup(views, ids) if views is not None \
            else jnp.take(params["embed"], ids, axis=0)

    with jax.named_scope("embed"):
        x = _scaled(embedded(tokens), dec.embed_scale)
        if dec.hyper is not None:       # every stream starts as the embedding
            x = (x,) * dec.hyper.streams
    layers = blocks = params["layers"]
    if next_tokens is not None:
        if cache is not None or dec.hyper is not None:
            raise ValueError(
                "a prediction module runs in a training forward of one "
                "residual stream: how several streams enter one is not built")
        blocks = [*layers, params["mtp"]["block"]]
    keys = _block_keys(dec, blocks)

    extras = _planned_extras(dec, blocks, x, params["embed"].shape[0],
                             losses=1 + (next_tokens is not None)) \
        if cache is None else ((),) * len(layers)

    @functools.cache
    def block_at(key: Tuple, extra: Tuple[str, ...]):
        block = _block_of(dec, *key)
        if dec.remat is not None and cache is None:    # remat is training's
            policy = dec.remat
            if extra or (policy is keep_kernel_outputs
                         and key[0] in KEPT_BY_KIND):
                policy = jax.checkpoint_policies.save_only_these_names(
                    *_kept(key[0]), *extra)
            block = jax.checkpoint(block, policy=policy)
        return block

    per_layer, new_cache, shared = [], [], Shared()
    with jax.named_scope("layers"):
        for key, extra, layer, cache_layer in zip(
                keys, extras, layers, cache or [None] * len(layers)):
            x, stats, cache_layer, shared = block_at(key, extra)(
                x, layer, cache_layer, start_pos, shared)
            per_layer += [] if stats is None else [stats]
            new_cache.append(cache_layer)
    if next_tokens is not None:
        with jax.named_scope("mtp"):
            with jax.named_scope("mtp_embed"):
                rows = _scaled(embedded(next_tokens), dec.embed_scale)
            x_next, stats = prediction_module(
                x, rows, params["mtp"], block_at(keys[-1], extras[-1]),
                dec.norm_eps)
            x_next = _scaled(x_next, dec.logit_scale)
            per_layer += [] if stats is None else [stats]
    with jax.named_scope("final_norm"):
        if dec.hyper is not None:       # the streams' sum is what is normed
            x = sum(t.astype(jnp.float32) for t in x).astype(x[0].dtype)
        x = _scaled(_norm(x, params, "lnf", dec.norm_eps), dec.logit_scale)
    if views is not None:
        head = views.swapaxes(1, 2)
    else:
        head = params["head"] if "head" in params else params["embed"].T
    if next_tokens is not None:
        x = (x, x_next)
    return x, head, per_layer, (new_cache if cache is not None else None)


def decoder_logits(x, head):
    """decoder_hidden's rows and head -> float32 logits [b, L, vocab]."""
    if head.ndim == 3:          # chip_views: every view is the one table
        head = head[0]
    return jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
