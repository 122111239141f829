"""Autoregressive generation with a KV cache (GPT family).

Parity role: the reference serves LLMs by hosting external engines
(vLLM etc.) on its actors; here the decode path is native — a
fixed-shape KV cache (static shapes: one XLA compile for prefill per
prompt bucket, one for the single-token decode step), rotary offsets per
position, fp32 logits. The serving layer (llm.serving) drives these
jitted steps and streams tokens through Serve.

Cache layout: per layer {"k"|"v": [batch, heads, max_len, head_dim]}.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import DEFAULT_MASK_VALUE
from ..ops.layers import rms_norm, rope
from .gpt import GPTConfig


def init_cache(cfg: GPTConfig, batch: int, max_len: int) -> List[Dict]:
    h, hd = cfg.n_heads, cfg.head_dim
    return [
        {"k": jnp.zeros((batch, h, max_len, hd), cfg.dtype),
         "v": jnp.zeros((batch, h, max_len, hd), cfg.dtype)}
        for _ in range(cfg.n_layers)
    ]


def _cached_block(x, layer, cache_layer, start_pos, cfg: GPTConfig):
    """One transformer block reading/writing the KV cache.

    x: [b, L, d]. `start_pos` is the absolute offset of x's positions —
    a scalar (all rows aligned: prefill / single-stream decode) or a
    [b] vector (continuous batching: every row decodes at its own
    position). One implementation serves both so the attention formulas
    can't diverge; only the cache write and causal mask specialize on
    the index shape. Returns (x_out, new_cache_layer).
    """
    b, L, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    max_len = cache_layer["k"].shape[-2]
    sp = jnp.asarray(start_pos)
    per_row = sp.ndim == 1

    y = rms_norm(x, layer["ln1"])
    qkv = jnp.einsum("bsd,de->bse", y, layer["wqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, L, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, L, h, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, L, h, hd).transpose(0, 2, 1, 3)
    # Rotary embeddings at absolute (possibly traced) positions —
    # the same rope() the training forward uses ([L] or [b, L]).
    if per_row:
        positions = sp[:, None] + jnp.arange(L)[None]
    else:
        positions = sp + jnp.arange(L)
    q = rope(q, positions=positions)
    k = rope(k, positions=positions)

    if per_row:
        rows = jnp.arange(b)[:, None]                    # (b, 1)
        cols = sp[:, None] + jnp.arange(L)[None]         # (b, L)
        # Advanced indexing on axes 0 and 2 moves the index dims to
        # the front: value shape (b, L, h, hd).
        k_cache = cache_layer["k"].at[rows, :, cols, :].set(
            k.transpose(0, 2, 1, 3).astype(cache_layer["k"].dtype))
        v_cache = cache_layer["v"].at[rows, :, cols, :].set(
            v.transpose(0, 2, 1, 3).astype(cache_layer["v"].dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache_layer["k"], k.astype(cache_layer["k"].dtype),
            (0, 0, sp, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache_layer["v"], v.astype(cache_layer["v"].dtype),
            (0, 0, sp, 0))

    scale = hd ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (L, max_len), 1)
    if per_row:
        q_pos = sp[:, None, None] + q_iota[None]         # (b, L, max)
        mask = (k_pos[None] <= q_pos)[:, None]           # (b,1,L,max)
    else:
        mask = (k_pos <= sp + q_iota)[None, None]        # (1,1,L,max)
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_cache.dtype),
                      v_cache)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, d)
    x = x + jnp.einsum("bsd,de->bse", attn, layer["wo"])
    y = rms_norm(x, layer["ln2"])
    hidden = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, layer["w1"]))
    x = x + jnp.einsum("bsf,fd->bsd", hidden, layer["w2"])
    return x, {"k": k_cache, "v": v_cache}


def cached_forward(params: Dict, tokens, cache: List[Dict],
                   start_pos, cfg: GPTConfig
                   ) -> Tuple[jnp.ndarray, List[Dict]]:
    """Forward over `tokens` [b, L] at absolute offset start_pos using
    (and updating) the cache. Returns (logits [b, L, vocab] fp32,
    new_cache)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    new_cache = []
    for layer, cache_layer in zip(params["layers"], cache):
        x, cl = _cached_block(x, layer, cache_layer, start_pos, cfg)
        new_cache.append(cl)
    x = rms_norm(x, params["lnf"])
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    return (jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32),
            new_cache)


@functools.lru_cache(maxsize=8)
def make_generate_fns(cfg: GPTConfig, max_len: int):
    """(prefill, decode_step) jitted with donated caches, cached per
    (cfg, max_len) so repeated serving requests reuse the XLA compiles
    (the lru key is why max_len is a parameter — caches passed in must
    have this length).

    prefill(params, tokens[b, Lp], cache) -> (last_logits[b, vocab], cache)
    decode_step(params, token[b], pos, cache) -> (logits[b, vocab], cache)
    """

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(params, tokens, cache):
        with jax.named_scope("prefill"):
            logits, cache = cached_forward(params, tokens, cache, 0, cfg)
        return logits[:, -1, :], cache

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode_step(params, token, pos, cache):
        with jax.named_scope("decode"):
            logits, cache = cached_forward(
                params, token[:, None], cache, pos, cfg)
        return logits[:, 0, :], cache

    return prefill, decode_step


@functools.lru_cache(maxsize=8)
def make_continuous_fns(cfg: GPTConfig, max_len: int, batch: int):
    """(insert_prefill, decode_batch) for CONTINUOUS BATCHING: one
    shared [batch, ...] KV cache whose slots belong to independent
    requests. A new request prefills into a free slot while the other
    slots keep decoding; decode_batch advances EVERY slot one token at
    its own position per call (per-slot rotary offsets + causal masks).
    TPU-native analogue of vLLM-style continuous batching: static
    shapes (one compile per prompt bucket + one decode compile), slot
    reuse instead of dynamic batch shapes, so XLA never recompiles as
    requests come and go.

    insert_prefill(params, tokens[1, Lp], cache, slot, true_len)
        -> (last_logits[vocab], cache)  # logits at true_len-1; the
        prompt may be right-padded to the Lp bucket, padding positions
        are never read back (decode overwrites position p before any
        read at p).
    decode_batch(params, tokens[B], pos[B], cache)
        -> (logits[B, vocab], cache)
    """
    @functools.partial(jax.jit, donate_argnums=(2,))
    def insert_prefill(params, tokens, cache, slot, true_len):
        sub = [{k: jax.lax.dynamic_slice_in_dim(cl[k], slot, 1, axis=0)
                for k in ("k", "v")} for cl in cache]
        with jax.named_scope("prefill"):
            logits, new_sub = cached_forward(params, tokens, sub, 0, cfg)
        out = [{k: jax.lax.dynamic_update_slice_in_dim(
                    cl[k], ns[k], slot, axis=0) for k in ("k", "v")}
               for cl, ns in zip(cache, new_sub)]
        last = jax.lax.dynamic_slice_in_dim(
            logits[0], true_len - 1, 1, axis=0)[0]
        return last, out

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode_batch(params, tokens, pos, cache):
        # cached_forward with a PER-ROW start_pos vector — the same
        # block implementation as prefill and sequential decode.
        with jax.named_scope("decode"):
            logits, cache = cached_forward(
                params, tokens[:, None], cache, pos, cfg)
        return logits[:, 0, :], cache

    return insert_prefill, decode_batch


def _bucket_len(n: int, cap: int) -> int:
    """Round up to a power of two (min 64), capped — a handful of cache
    lengths instead of one compile per prompt length."""
    b = 64
    while b < n:
        b *= 2
    return min(b, cap)


def sample_token(logits, key, temperature: float = 0.0):
    """Greedy (temperature 0) or temperature sampling; [b, vocab] -> [b]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def generate(params: Dict, cfg: GPTConfig, prompt,
             max_new_tokens: int = 32, temperature: float = 0.0,
             max_len: Optional[int] = None, seed: int = 0,
             stop_token: Optional[int] = None):
    """Generator yielding one [batch] token array per step (so callers —
    e.g. a Serve replica — can stream them)."""
    prompt = jnp.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None]
    b, lp = prompt.shape
    total = max_len or _bucket_len(lp + max_new_tokens, cfg.max_seq_len)
    if not lp + max_new_tokens <= total <= cfg.max_seq_len:
        raise ValueError(
            f"prompt ({lp}) + max_new_tokens ({max_new_tokens}) must fit "
            f"in max_len ({total}) <= cfg.max_seq_len "
            f"({cfg.max_seq_len})")
    prefill, decode_step = make_generate_fns(cfg, total)
    cache = init_cache(cfg, b, total)
    logits, cache = prefill(params, prompt, cache)
    key = jax.random.PRNGKey(seed)
    pos = lp
    for i in range(max_new_tokens):
        key, sub = jax.random.split(key)
        token = sample_token(logits, sub, temperature)
        yield token
        if stop_token is not None and bool(
                jnp.all(token == stop_token)):
            return
        if i + 1 < max_new_tokens:  # last sample needs no next logits
            logits, cache = decode_step(params, token, pos, cache)
            pos += 1
