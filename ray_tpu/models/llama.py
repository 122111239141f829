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
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION, Decoder, decoder_hidden, decoder_logits,
                      swiglu_mlp)


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

    def decoder(self) -> Decoder:
        """GQA from `wq` + `wkv` (n_kv_heads heads of k and of v), rotary
        positions at `rope_base`, RMSNorm at ops.layers' eps, a SwiGLU MLP."""
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, mlp=(swiglu_mlp,) * self.n_layers,
            remat=(jax.checkpoint_policies.nothing_saveable
                   if self.remat else None),
            kinds=(ATTENTION,) * self.n_layers, rope_base=self.rope_base)

    def init(self, key) -> Dict:
        return llama_init(key, self)

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2,
                   n_layers=2, d_ff=96, max_seq_len=128)


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


def llama_forward(params: Dict, tokens, cfg: LlamaConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def llama_loss(params: Dict, batch: Tuple, cfg: LlamaConfig):
    tokens, targets = batch
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return cross_entropy(x, head, targets)


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
