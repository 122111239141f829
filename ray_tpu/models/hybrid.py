"""Hybrid state-space / attention decoder (Granite-4.0-H style), TPU-first.

A decoder whose layers are not alike: `layer_types` says, layer by layer,
whether the sequence mixer is a Mamba-2 layer (models.decoder.mamba2 over
ops.ssm_scan: input projection, causal depthwise convolution, selective
scan, gated RMSNorm, output projection) or grouped-query attention with no
positional encoding at all, its scores scaled by `attention_multiplier`.
Every layer has the dense SwiGLU MLP. The embedding is multiplied by
`embedding_multiplier`, both residual branches by `residual_multiplier`,
and the logits divided by `logits_scaling`; the head is the embedding.
`HybridConfig.granite_4_0_h_micro()` is ibm-granite/granite-4.0-h-micro's
config.json (model_type granitemoehybrid with no routed experts).

Same conventions as models.gpt: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, softplus, decays and state. `decoder()` names
every layer's kind from `layer_types`: of models.decoder's eight (MIXERS)
this family has two, `mamba` a MAMBA2 layer, cache {"conv": [batch, d_conv
- 1, inner + 2 groups x state], "ssm": [batch, heads, head_dim, state]
float32}; `attention` an ATTENTION layer from `wq` + `wkv`, cache {"k" |
"v": [batch, n_kv_heads, max_len, head_dim]}.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (MAMBA2, Decoder, decoder_hidden, decoder_logits,
                      keep_kernel_outputs, swiglu_mlp)

MAMBA, ATTENTION = "mamba", "attention"     # ATTENTION is the decoder's too


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = shared_intermediate_size,
    n_heads / n_kv_heads = num_attention_heads / num_key_value_heads)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 2
    layer_types: Tuple[str, ...] = (MAMBA, MAMBA, ATTENTION)
    d_ff: int = 2048
    mamba_n_heads: int = 16
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_multiplier: float = 0.125
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert set(self.layer_types) <= {MAMBA, ATTENTION}, self.layer_types
        assert self.n_heads % self.n_kv_heads == 0
        assert self.mamba_n_heads % self.mamba_n_groups == 0

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def decoder(self) -> Decoder:
        """GQA from `wq` + `wkv` with no rotary, scores scaled by
        `attention_multiplier`; Mamba-2 layers at the `mamba_*` sizes; a
        SwiGLU MLP; the three multipliers; under `remat` a block keeps
        what its kernels made and makes the rest again."""
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, mlp=(swiglu_mlp,) * self.n_layers,
            remat=keep_kernel_outputs if self.remat else None,
            kinds=tuple(MAMBA2 if kind == MAMBA else ATTENTION
                        for kind in self.layer_types),
            rope_base=None, norm_eps=self.norm_eps,
            sm_scale=self.attention_multiplier,
            residual_scale=self.residual_multiplier,
            embed_scale=self.embedding_multiplier,
            logit_scale=1.0 / self.logits_scaling,
            ssm_heads=self.mamba_n_heads, ssm_head_dim=self.mamba_d_head,
            ssm_state=self.mamba_d_state, ssm_groups=self.mamba_n_groups,
            ssm_chunk=self.mamba_chunk_size)

    def init(self, key) -> Dict:
        return hybrid_init(key, self)

    @classmethod
    def tiny(cls) -> "HybridConfig":
        """Two Mamba-2 layers and one attention layer (4 : 1 GQA), chunks
        of 8: the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=1,
                   layer_types=(MAMBA, MAMBA, ATTENTION), d_ff=96,
                   mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
                   mamba_chunk_size=8, attention_multiplier=1.0 / 64,
                   embedding_multiplier=12.0, residual_multiplier=0.22,
                   logits_scaling=8.0, max_seq_len=64)

    @classmethod
    def granite_4_0_h_micro(cls) -> "HybridConfig":
        """ibm-granite/granite-4.0-h-micro: 40 layers, attention at 5, 15,
        25 and 35, Mamba-2 everywhere else; 3.19 B parameters."""
        period = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
        return cls(vocab_size=100352, d_model=2048, n_heads=32, n_kv_heads=8,
                   layer_types=period * 4, d_ff=8192, mamba_n_heads=64,
                   mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
                   mamba_d_conv=4, mamba_chunk_size=256,
                   attention_multiplier=0.015625, embedding_multiplier=12.0,
                   residual_multiplier=0.22, logits_scaling=8.0,
                   norm_eps=1e-5, max_seq_len=131072)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _mamba_init(key, cfg: HybridConfig, out_scale: float) -> Dict:
    """Mamba-2's own initialisation where config.json gives none: A =
    -U[1, 16], the step's bias the inverse softplus of a log-uniform
    [1e-3, 1e-1], D = 1, the convolution as torch's Conv1d (uniform in
    +-1/sqrt(taps))."""
    k_in, k_out, k_cw, k_cb, k_a, k_dt = jax.random.split(key, 6)
    d, H, taps = cfg.d_model, cfg.mamba_n_heads, cfg.mamba_d_conv
    dt = jnp.exp(jax.random.uniform(
        k_dt, (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
    bound = taps ** -0.5
    return {
        "in_proj": _normal(
            k_in, (d, cfg.mamba_inner + cfg.mamba_conv_dim + H), d ** -0.5,
            cfg.dtype),
        "conv_w": jax.random.uniform(
            k_cw, (cfg.mamba_conv_dim, taps), minval=-bound,
            maxval=bound).astype(cfg.dtype),
        "conv_b": jax.random.uniform(
            k_cb, (cfg.mamba_conv_dim,), minval=-bound,
            maxval=bound).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k_a, (H,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "ssm_norm": jnp.ones((cfg.mamba_inner,), jnp.float32),
        "out_proj": _normal(k_out, (cfg.mamba_inner, d),
                            cfg.mamba_inner ** -0.5 * out_scale, cfg.dtype),
    }


def _attention_init(key, cfg, out_scale: float) -> Dict:
    """`wq`, `wkv`, `wo` (models.olmo_hybrid's too: `cfg` gives d_model,
    n_kv_heads, head_dim and dtype)."""
    kq, kkv, ko = jax.random.split(key, 3)
    d, kv_d = cfg.d_model, cfg.n_kv_heads * cfg.head_dim
    return {
        "wq": _normal(kq, (d, d), d ** -0.5, cfg.dtype),
        "wkv": _normal(kkv, (d, 2 * kv_d), d ** -0.5, cfg.dtype),
        "wo": _normal(ko, (d, d), d ** -0.5 * out_scale, cfg.dtype),
    }


def _mlp_init(keys, cfg, out_scale: float) -> Dict:
    """The dense SwiGLU MLP's three matrices from three keys (models.
    olmo_hybrid's too: `cfg` gives d_model, d_ff and dtype)."""
    kg, ku, kd = keys
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": _normal(kg, (d, f), d ** -0.5, cfg.dtype),
        "w_up": _normal(ku, (d, f), d ** -0.5, cfg.dtype),
        "w_down": _normal(kd, (f, d), f ** -0.5 * out_scale, cfg.dtype),
    }


def _layer_init(key, kind: str, cfg: HybridConfig) -> Dict:
    k_mix, *k_mlp = jax.random.split(key, 4)
    d = cfg.d_model
    out_scale = (2 * cfg.n_layers) ** -0.5
    mixer = _mamba_init if kind == MAMBA else _attention_init
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        **mixer(k_mix, cfg, out_scale),
        "ln2": jnp.ones((d,), jnp.float32),
        **_mlp_init(k_mlp, cfg, out_scale),
    }


def hybrid_init(key, cfg: HybridConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 1)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         cfg.d_model ** -0.5, cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [_layer_init(keys[i + 1], kind, cfg)
                   for i, kind in enumerate(cfg.layer_types)],
    }


def hybrid_param_axes(cfg: HybridConfig) -> Dict:
    mlp = {"ln1": ("embed",), "ln2": ("embed",),
           "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
           "w_down": ("mlp", "embed")}
    mixers = {
        MAMBA: {"in_proj": ("embed", None), "conv_w": (None, None),
                "conv_b": (None,), "dt_bias": (None,), "A_log": (None,),
                "D": (None,), "ssm_norm": (None,),
                "out_proj": (None, "embed")},
        ATTENTION: {"wq": ("embed", "mlp"), "wkv": ("embed", "mlp"),
                    "wo": ("mlp", "embed")},
    }
    return {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "layers": [{**mlp, **mixers[kind]} for kind in cfg.layer_types],
    }


def hybrid_forward(params: Dict, tokens, cfg: HybridConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def hybrid_loss(params: Dict, batch: Tuple, cfg: HybridConfig):
    """Next-token cross entropy; the rows come scaled by 1 /
    logits_scaling from decoder_hidden, so the loss is ops.loss's as it is."""
    tokens, targets = batch
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return cross_entropy(x, head, targets)


def make_hybrid_train_step(cfg: HybridConfig, optimizer=None,
                           donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: hybrid_init(key, cfg),
        lambda params, batch: hybrid_loss(params, batch, cfg),
        axes=hybrid_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)
