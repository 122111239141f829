"""Autoregressive generation with a cache, for any config with a
`decoder()` and an `init(key)` (every decoder family of ray_tpu.models:
gpt, llama, moe, hybrid, sambay, olmo_hybrid, nemotron_h, lfm2_moe, xing4,
glm4_moe_lite, keye_vl2, bailing_hybrid). No family is named here.

Parity role: the reference serves LLMs by hosting external engines
(vLLM etc.) on its actors; here the decode path is native — a
fixed-shape KV cache (static shapes: one XLA compile for prefill per
prompt bucket, one for the single-token decode step), rotary offsets per
position, fp32 logits. The serving layer (llm.serving) drives these
jitted steps and streams tokens through Serve.

The cache is models.decoder's: one dict a layer, the state of the layer's
kind by `decoder.MIXERS` (the kinds and each one's layout are in that
module's docstring), every leaf with the batch first, which is what lets
`make_continuous_fns` cut one request's slot out of axis 0 of every leaf.
A family names its layers' kinds in `cfg.decoder().kinds`; the sizes come
from the shapes of the weights `cfg.init` would make, and none is made.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .decoder import decoder_hidden, decoder_logits, empty_cache


# The shapes of a model's layers, with no weight made; kept a config
# because tracing an init costs tens of milliseconds and `generate` asks
# for a cache a request.
_shapes = functools.lru_cache(maxsize=8)(
    lambda cfg: jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"])


def init_cache(cfg, batch: int, max_len: int) -> List[Dict]:
    """An empty cache: each layer gets the state of its kind, sized from
    the shapes of the model's weights."""
    return empty_cache(cfg.decoder(), _shapes(cfg), batch, max_len, cfg.dtype)


def cached_forward(params: Dict, tokens, cache: List[Dict],
                   start_pos, cfg) -> Tuple[jnp.ndarray, List[Dict]]:
    """Forward over `tokens` [b, L] at absolute offset start_pos (a
    scalar, or one offset a row) using (and updating) the cache. Returns
    (logits [b, L, vocab] fp32, new_cache)."""
    x, head, _, new_cache = decoder_hidden(
        params, tokens, cfg.decoder(), cache, start_pos)
    return decoder_logits(x, head), new_cache


@functools.lru_cache(maxsize=8)
def make_generate_fns(cfg, max_len: int):
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
def make_continuous_fns(cfg, max_len: int, batch: int):
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
                for k in cl} for cl in cache]
        with jax.named_scope("prefill"):
            logits, new_sub = cached_forward(params, tokens, sub, 0, cfg)
        out = [{k: jax.lax.dynamic_update_slice_in_dim(
                    cl[k], ns[k], slot, axis=0) for k in cl}
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


def generate(params: Dict, cfg, prompt,
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
