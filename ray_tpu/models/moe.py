"""Mixture-of-Experts decoder (Mixtral-style), TPU-first.

Net-new vs the reference (SURVEY.md §2.4: EP "Absent"): a GPT-family
decoder whose MLP is a top-2 routed expert layer
(parallel.moe.moe_layer). Single-mesh execution computes experts with
batched einsums; under shard_map with an `ep` axis the layer all_to_alls
tokens to their experts' shards (pass axis_name via cfg.ep_axis).

Same conventions as models.gpt: dict pytrees, logical axis tables
(experts carry a leading 'expert' axis that partition rules map to the
ep mesh axis), bf16 matmuls / fp32 routing and norms.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import flash_attention
from ..ops.layers import rms_norm, rope
from ..ops.loss import cross_entropy
from ..parallel.moe import moe_layer


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    n_experts: int = 8
    d_ff: int = 1024
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Mesh axis name for expert parallelism (used inside shard_map);
    # None = single-shard dense-dispatch path.
    ep_axis: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls) -> "MoEConfig":
        return cls(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                   n_experts=4, d_ff=96, max_seq_len=64)


def _layer_init(key, cfg: MoEConfig) -> Dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    scale = d ** -0.5
    out_scale = scale / (2 * cfg.n_layers) ** 0.5
    return {
        "ln1": jnp.ones((d,), dtype=jnp.float32),
        "wqkv": (jax.random.normal(k1, (d, 3 * d)) * scale
                 ).astype(cfg.dtype),
        "wo": (jax.random.normal(k2, (d, d)) * out_scale
               ).astype(cfg.dtype),
        "ln2": jnp.ones((d,), dtype=jnp.float32),
        # Router weights stay fp32: routing decisions are
        # precision-sensitive (flips reroute whole tokens).
        "gate": jax.random.normal(k3, (d, e)) * scale,
        "expert_w1": (jax.random.normal(k4, (e, d, f)) * scale
                      ).astype(cfg.dtype),
        "expert_w2": (jax.random.normal(k5, (e, f, d)) * out_scale
                      ).astype(cfg.dtype),
    }


def moe_init(key, cfg: MoEConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 1)
    return {
        "embed": (jax.random.normal(keys[0],
                                    (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), dtype=jnp.float32),
        "layers": [_layer_init(keys[i + 1], cfg)
                   for i in range(cfg.n_layers)],
    }


def moe_param_axes(cfg: MoEConfig) -> Dict:
    layer = {
        "ln1": ("embed",),
        "wqkv": ("embed", "mlp"),
        "wo": ("mlp", "embed"),
        "ln2": ("embed",),
        "gate": ("embed", None),
        "expert_w1": ("expert", "embed", "mlp"),
        "expert_w2": ("expert", "mlp", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def _block(x, layer, cfg: MoEConfig):
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    y = rms_norm(x, layer["ln1"])
    qkv = jnp.einsum("bsd,de->bse", y, layer["wqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = rope(q.reshape(b, s, h, hd).transpose(0, 2, 1, 3))
    k = rope(k.reshape(b, s, h, hd).transpose(0, 2, 1, 3))
    v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    attn = flash_attention(q, k, v, True, None)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + jnp.einsum("bsd,de->bse", attn, layer["wo"])
    # Routed expert MLP over flattened tokens
    y = rms_norm(x, layer["ln2"])
    flat = y.reshape(b * s, d)
    out, aux = moe_layer(flat, layer["gate"], layer["expert_w1"],
                         layer["expert_w2"],
                         capacity_factor=cfg.capacity_factor,
                         axis_name=cfg.ep_axis)
    x = x + out.reshape(b, s, d)
    return x, aux


def _hidden(params: Dict, tokens, cfg: MoEConfig):
    """tokens [b, s] -> (final-norm rows [b, s, d], aux_loss scalar)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    aux_total = jnp.zeros((), jnp.float32)
    block = functools.partial(_block, cfg=cfg)
    if cfg.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)
    for layer in params["layers"]:
        x, aux = block(x, layer)
        aux_total = aux_total + aux
    return rms_norm(x, params["lnf"]), aux_total / len(params["layers"])


def moe_forward(params: Dict, tokens, cfg: MoEConfig):
    """tokens [b, s] -> (logits [b, s, vocab] fp32, aux_loss scalar)."""
    x, aux = _hidden(params, tokens, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, params["embed"].T
                        ).astype(jnp.float32)
    return logits, aux


def moe_loss(params: Dict, batch: Tuple, cfg: MoEConfig):
    tokens, targets = batch
    x, aux = _hidden(params, tokens, cfg)
    return (cross_entropy(x, params["embed"].T, targets)
            + cfg.aux_loss_weight * aux)


def make_moe_train_step(cfg: MoEConfig, optimizer=None,
                        donate: bool = True, mesh=None, rules=None):
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: moe_init(key, cfg),
        lambda params, batch: moe_loss(params, batch, cfg),
        axes=moe_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)
