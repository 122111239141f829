"""Hybrid linear-attention / attention decoder (Olmo-Hybrid style),
TPU-first.

`layer_types` says, layer by layer, whether the sequence mixer is a Gated
DeltaNet layer (`linear_attention`: models.decoder.gated_delta over
ops.gated_delta — one projection to q | k | v, a causal depthwise
convolution with no bias, L2-normalised q and k, the gated delta rule with
beta in (0, 2), an RMSNorm a head then a gate, the output projection) in
the layer's own pre-norm block, x + mixer(norm(x)), x + mlp(norm(x)); or
full multi-head attention (`full_attention`) with q and k RMS-normed over
all their columns and no rotary unless `rope_theta` gives a base, in OLMo
2's reordered block that norms what a branch RETURNS, x + norm(attention
(x)), x + norm(mlp(x)). Every layer has the dense SwiGLU MLP; the head is
untied. `OlmoHybridConfig.olmo_hybrid_7b()` is allenai/Olmo-Hybrid-7B's
config.json (model_type olmo_hybrid).

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, softplus, sigmoid, decays and state. `decoder()`
names every layer's kind from `layer_types` (`linear_attention`:
models.decoder's GATED_DELTA; `full_attention`: its ATTENTION); where a
layer's norms sit it still says by the weights it holds (`post_attention`,
`post_feedforward` and no `ln1`, `ln2`: norms after the branches). Cache: a
linear-attention layer {"conv": [batch, taps - 1, heads x (2 K + V)],
"delta": [batch, heads, K, V] float32}, which does not grow; a
full-attention layer {"k" | "v": [batch, heads, max_len, head_dim]}.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION, GATED_DELTA, Decoder, decoder_hidden,
                      decoder_logits, keep_kernel_outputs, swiglu_mlp)
from .hybrid import _attention_init, _mlp_init, _normal

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, n_heads =
    num_attention_heads = num_key_value_heads: the model is MHA)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    d_ff: int = 2048
    linear_num_heads: int = 8       # key heads = value heads
    linear_key_head_dim: int = 48
    linear_value_head_dim: int = 96
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 64
    rope_theta: Optional[float] = None      # None: no positions at all
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert set(self.layer_types) <= {LINEAR, FULL}, self.layer_types
        assert self.d_model % self.n_heads == 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        return 2 * self.linear_key_dim + self.linear_value_dim

    def decoder(self) -> Decoder:
        """MHA from `wq` + `wkv` with `q_norm` / `k_norm`, rotary only
        under a `rope_theta`; delta-rule layers in chunks of
        `linear_chunk_size`; a SwiGLU MLP; no multipliers; under `remat` a
        block keeps what its kernels made and makes the rest again."""
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.head_dim, mlp=(swiglu_mlp,) * self.n_layers,
            remat=keep_kernel_outputs if self.remat else None,
            kinds=tuple(GATED_DELTA if kind == LINEAR else ATTENTION
                        for kind in self.layer_types),
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            delta_chunk=self.linear_chunk_size)

    def init(self, key) -> Dict:
        return olmo_hybrid_init(key, self)

    @classmethod
    def tiny(cls) -> "OlmoHybridConfig":
        """Three delta-rule layers and one attention layer, 3 heads of
        12 x 20 (no multiple of any tile), chunks of 8: the CPU tests'
        size."""
        return cls(vocab_size=256, d_model=64, n_heads=4, d_ff=96,
                   linear_num_heads=3, linear_key_head_dim=12,
                   linear_value_head_dim=20, linear_chunk_size=8,
                   max_seq_len=64)

    @classmethod
    def olmo_hybrid_7b(cls) -> "OlmoHybridConfig":
        """allenai/Olmo-Hybrid-7B: 32 layers, three Gated DeltaNet layers
        to one full-attention layer; 7.43 B parameters."""
        return cls(vocab_size=100352, d_model=3840, n_heads=30,
                   layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 8,
                   d_ff=11008, linear_num_heads=30, linear_key_head_dim=96,
                   linear_value_head_dim=192, linear_conv_kernel_dim=4,
                   rope_theta=None, norm_eps=1e-6, max_seq_len=65536)


def _linear_init(key, cfg: OlmoHybridConfig, out_scale: float) -> Dict:
    """config.json gives no initialisation: the decay's as Mamba-2's
    (models.hybrid._mamba_init: A = -U[1, 16], the step's bias the inverse
    softplus of a log-uniform [1e-3, 1e-1]), the convolution as torch's
    Conv1d with no bias, the head norm's weight 1."""
    k_in, k_ab, k_g, k_out, k_cw, k_a, k_dt = jax.random.split(key, 7)
    d, H, taps = cfg.d_model, cfg.linear_num_heads, cfg.linear_conv_kernel_dim
    dt = jnp.exp(jax.random.uniform(
        k_dt, (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
    bound = taps ** -0.5
    return {
        "delta_in": _normal(k_in, (d, cfg.linear_conv_dim), d ** -0.5,
                            cfg.dtype),
        "delta_ab": _normal(k_ab, (d, 2 * H), d ** -0.5, cfg.dtype),
        "delta_gate": _normal(k_g, (d, cfg.linear_value_dim), d ** -0.5,
                              cfg.dtype),
        "conv_w": jax.random.uniform(
            k_cw, (cfg.linear_conv_dim, taps), minval=-bound,
            maxval=bound).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k_a, (H,), minval=1.0,
                                            maxval=16.0)),
        "delta_norm": jnp.ones((cfg.linear_value_head_dim,), jnp.float32),
        "delta_out": _normal(k_out, (cfg.linear_value_dim, d),
                             cfg.linear_value_dim ** -0.5 * out_scale,
                             cfg.dtype),
    }


def _layer_init(key, kind: str, cfg: OlmoHybridConfig) -> Dict:
    k_mix, *k_mlp = jax.random.split(key, 4)
    d = cfg.d_model
    out_scale = (2 * cfg.n_layers) ** -0.5

    def ones():
        return jnp.ones((d,), jnp.float32)

    mlp = _mlp_init(k_mlp, cfg, out_scale)
    if kind == LINEAR:
        return {"ln1": ones(), **_linear_init(k_mix, cfg, out_scale),
                "ln2": ones(), **mlp}
    return {**_attention_init(k_mix, cfg, out_scale), "q_norm": ones(),
            "k_norm": ones(), "post_attention": ones(), **mlp,
            "post_feedforward": ones()}


def olmo_hybrid_init(key, cfg: OlmoHybridConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         cfg.d_model ** -0.5, cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), jnp.float32),
        "head": _normal(keys[1], (cfg.d_model, cfg.vocab_size),
                        cfg.d_model ** -0.5, cfg.dtype),
        "layers": [_layer_init(keys[i + 2], kind, cfg)
                   for i, kind in enumerate(cfg.layer_types)],
    }


def olmo_hybrid_param_axes(cfg: OlmoHybridConfig) -> Dict:
    mlp = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
           "w_down": ("mlp", "embed")}
    kinds = {
        LINEAR: {"ln1": ("embed",), "ln2": ("embed",),
                 "delta_in": ("embed", None), "delta_ab": ("embed", None),
                 "delta_gate": ("embed", None), "conv_w": (None, None),
                 "dt_bias": (None,), "A_log": (None,),
                 "delta_norm": (None,), "delta_out": (None, "embed")},
        FULL: {"wq": ("embed", "mlp"), "wkv": ("embed", "mlp"),
               "wo": ("mlp", "embed"), "q_norm": ("mlp",),
               "k_norm": ("mlp",), "post_attention": ("embed",),
               "post_feedforward": ("embed",)},
    }
    return {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "head": ("embed", "vocab"),
        "layers": [{**mlp, **kinds[kind]} for kind in cfg.layer_types],
    }


def olmo_hybrid_forward(params: Dict, tokens, cfg: OlmoHybridConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def olmo_hybrid_loss(params: Dict, batch: Tuple, cfg: OlmoHybridConfig):
    """Next-token cross entropy (ops.loss, over the untied head)."""
    tokens, targets = batch
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return cross_entropy(x, head, targets)


def make_olmo_hybrid_train_step(cfg: OlmoHybridConfig, optimizer=None,
                                donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: olmo_hybrid_init(key, cfg),
        lambda params, batch: olmo_hybrid_loss(params, batch, cfg),
        axes=olmo_hybrid_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)
