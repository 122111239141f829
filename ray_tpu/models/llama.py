"""Llama-family decoder: grouped-query attention + SwiGLU, TPU-first.

Parity role: the reference orchestrates external torch Llama fine-tunes
(train/examples/deepspeed, accelerate — SURVEY.md §2.4 FSDP row); here the
model family is native. Differences from models.gpt: separate q/kv
projections with n_kv_heads < n_heads (GQA — KV cache and kv matmuls
shrink by n_heads/n_kv_heads), SwiGLU MLP, untied output head.

Same conventions as gpt.py: plain dict pytrees, logical axis tables for
parallel.partition, bf16 matmuls / fp32 norms, per-block remat.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import flash_attention
from ..ops.layers import rms_norm, rope
from ..ops.loss import cross_entropy


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 2
    n_layers: int = 6
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_base: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def group_size(self) -> int:
        return self.n_heads // self.n_kv_heads

    def __post_init__(self):
        assert self.n_heads % self.n_kv_heads == 0, \
            "n_heads must be a multiple of n_kv_heads"

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2,
                   n_layers=2, d_ff=96, max_seq_len=128)

    @classmethod
    def tpu_bench(cls) -> "LlamaConfig":
        """Single-chip MFU-bench shape: head_dim 128 (MXU-native lane
        width — GPT-2's head_dim 64 half-fills the systolic array),
        4:1 GQA, S=2048,
        ~250M params so optimizer+activations fit v5e HBM without
        remat."""
        return cls(vocab_size=32000, d_model=1024, n_heads=8,
                   n_kv_heads=2, n_layers=16, d_ff=2816,
                   max_seq_len=2048, remat=False)


def _layer_init(key, cfg: LlamaConfig) -> Dict:
    kq, kkv, ko, kg, ku, kd = jax.random.split(key, 6)
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    kv_d = cfg.n_kv_heads * hd
    scale = d ** -0.5
    out_scale = scale / (2 * cfg.n_layers) ** 0.5
    return {
        "ln1": jnp.ones((d,), dtype=jnp.float32),
        "wq": (jax.random.normal(kq, (d, d)) * scale).astype(cfg.dtype),
        "wkv": (jax.random.normal(kkv, (d, 2 * kv_d)) * scale
                ).astype(cfg.dtype),
        "wo": (jax.random.normal(ko, (d, d)) * out_scale
               ).astype(cfg.dtype),
        "ln2": jnp.ones((d,), dtype=jnp.float32),
        "w_gate": (jax.random.normal(kg, (d, f)) * scale
                   ).astype(cfg.dtype),
        "w_up": (jax.random.normal(ku, (d, f)) * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(kd, (f, d)) * out_scale
                   ).astype(cfg.dtype),
    }


def llama_init(key, cfg: LlamaConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {
        "embed": (jax.random.normal(keys[0],
                                    (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), dtype=jnp.float32),
        "head": (jax.random.normal(keys[1],
                                   (cfg.d_model, cfg.vocab_size))
                 * cfg.d_model ** -0.5).astype(cfg.dtype),
        "layers": [_layer_init(keys[i + 2], cfg)
                   for i in range(cfg.n_layers)],
    }


def llama_param_axes(cfg: LlamaConfig) -> Dict:
    layer = {
        "ln1": ("embed",),
        "wq": ("embed", "mlp"),
        "wkv": ("embed", "mlp"),
        "wo": ("mlp", "embed"),
        "ln2": ("embed",),
        "w_gate": ("embed", "mlp"),
        "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "head": ("embed", "vocab"),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def _block(x, layer, cfg: LlamaConfig):
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    y = rms_norm(x, layer["ln1"])
    q = jnp.einsum("bsd,de->bse", y, layer["wq"])
    kv = jnp.einsum("bsd,de->bse", y, layer["wkv"])
    k, v = jnp.split(kv, 2, axis=-1)
    q = rope(q.reshape(b, s, h, hd).transpose(0, 2, 1, 3),
             base=cfg.rope_base)
    k = rope(k.reshape(b, s, kvh, hd).transpose(0, 2, 1, 3),
             base=cfg.rope_base)
    v = v.reshape(b, s, kvh, hd).transpose(0, 2, 1, 3)
    # GQA: replicate each kv head across its query group. XLA lowers the
    # repeat to a broadcast feeding the attention matmuls — no HBM copy of
    # the expanded kv is materialized outside the kernel.
    k = jnp.repeat(k, cfg.group_size, axis=1)
    v = jnp.repeat(v, cfg.group_size, axis=1)
    attn = flash_attention(q, k, v, True, None)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + jnp.einsum("bsd,de->bse", attn, layer["wo"])
    y = rms_norm(x, layer["ln2"])
    gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", y, layer["w_gate"]))
    up = jnp.einsum("bsd,df->bsf", y, layer["w_up"])
    x = x + jnp.einsum("bsf,fd->bsd", gate * up, layer["w_down"])
    return x


def _hidden(params: Dict, tokens, cfg: LlamaConfig):
    """Embedding + blocks + final norm: [b, s] -> [b, s, d]."""
    x = jnp.take(params["embed"], tokens, axis=0)
    block = functools.partial(_block, cfg=cfg)
    if cfg.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)
    for layer in params["layers"]:
        x = block(x, layer)
    return rms_norm(x, params["lnf"])


def llama_forward(params: Dict, tokens, cfg: LlamaConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x = _hidden(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["head"]
                      ).astype(jnp.float32)


def llama_loss(params: Dict, batch: Tuple, cfg: LlamaConfig):
    tokens, targets = batch
    return cross_entropy(_hidden(params, tokens, cfg), params["head"],
                         targets)


def make_llama_train_step(cfg: LlamaConfig, optimizer=None,
                          donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step); sharding via partition rules as in
    models.gpt.make_train_step."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: llama_init(key, cfg),
        lambda params, batch: llama_loss(params, batch, cfg),
        axes=llama_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)
