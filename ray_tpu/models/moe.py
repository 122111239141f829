"""Mixture-of-Experts decoder (OLMoE-style), TPU-first.

Net-new vs the reference (SURVEY.md §2.4: EP "Absent"): a decoder whose
MLP is a dropless top-k routed layer of SwiGLU experts
(parallel.moe.dropless_moe_layer over ops.grouped_matmul), with RMSNorm
on the whole of q and k before the head split, rotary positions, an
untied head, router probabilities that are not renormalised over the
chosen experts, and the load-balancing and router-z auxiliary losses of
OLMoE (arXiv:2409.02060; `MoEConfig.olmoe_1b_7b()` is
allenai/OLMoE-1B-7B's config.json). Every token reaches its
`experts_per_token` experts: there is no capacity to overflow.

Same conventions as models.gpt: dict pytrees, logical axis tables
(experts carry a leading 'expert' axis that partition rules map to the
ep mesh axis), bf16 matmuls / fp32 routing and norms.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION, Decoder, decoder_hidden, decoder_logits,
                      keep_kernel_outputs, routed_experts)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    n_experts: int = 8
    experts_per_token: int = 2
    d_expert: int = 1024            # width of one expert's SwiGLU
    qk_norm: bool = True            # RMSNorm over all of q and of k
    tie_embeddings: bool = False
    norm_topk_prob: bool = False    # renormalise the k router weights
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    aux_loss_weight: float = 0.01   # load-balancing loss
    z_loss_weight: float = 0.001    # router z-loss
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def decoder(self) -> Decoder:
        """MHA from a fused `wqkv` (q/k norm where the layers hold
        `q_norm`), rotary positions at `rope_theta`, routed experts;
        under `remat` a block keeps what its kernels and its row unsort
        made and makes the rest again."""
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.head_dim, kinds=(ATTENTION,) * self.n_layers,
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            mlp=(functools.partial(
                routed_experts, experts_per_token=self.experts_per_token,
                norm_topk_prob=self.norm_topk_prob),) * self.n_layers,
            remat=keep_kernel_outputs if self.remat else None)

    def init(self, key) -> Dict:
        return moe_init(key, self)

    @classmethod
    def tiny(cls) -> "MoEConfig":
        return cls(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                   n_experts=4, experts_per_token=2, d_expert=96,
                   max_seq_len=64)

    @classmethod
    def olmoe_1b_7b(cls) -> "MoEConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct: 6.92 B parameters, 1.3 B
        active a token. The loss weights are the OLMoE paper's."""
        return cls(vocab_size=50304, d_model=2048, n_heads=16,
                   n_layers=16, n_experts=64, experts_per_token=8,
                   d_expert=1024, max_seq_len=4096)


def _layer_init(key, cfg: MoEConfig) -> Dict:
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    scale = d ** -0.5
    out_scale = scale / (2 * cfg.n_layers) ** 0.5

    def normal(k, shape, s):
        return (jax.random.normal(k, shape) * s).astype(cfg.dtype)

    layer = {
        "ln1": jnp.ones((d,), dtype=jnp.float32),
        "wqkv": normal(k1, (d, 3 * d), scale),
        "wo": normal(k2, (d, d), out_scale),
        "ln2": jnp.ones((d,), dtype=jnp.float32),
        # Router weights stay fp32: routing decisions are
        # precision-sensitive (flips reroute whole tokens).
        "router": jax.random.normal(k3, (d, e)) * scale,
        "expert_gate": normal(k4, (e, d, f), scale),
        "expert_up": normal(k5, (e, d, f), scale),
        "expert_down": normal(k6, (e, f, d),
                              f ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        layer["q_norm"] = jnp.ones((d,), dtype=jnp.float32)
        layer["k_norm"] = jnp.ones((d,), dtype=jnp.float32)
    return layer


def moe_init(key, cfg: MoEConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {
        "embed": (jax.random.normal(keys[0],
                                    (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), dtype=jnp.float32),
        "layers": [_layer_init(keys[i + 2], cfg)
                   for i in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size))
            * cfg.d_model ** -0.5).astype(cfg.dtype)
    return params


def moe_param_axes(cfg: MoEConfig) -> Dict:
    layer = {
        "ln1": ("embed",),
        "wqkv": ("embed", "mlp"),
        "wo": ("mlp", "embed"),
        "ln2": ("embed",),
        "router": ("embed", None),
        "expert_gate": ("expert", "embed", "mlp"),
        "expert_up": ("expert", "embed", "mlp"),
        "expert_down": ("expert", "mlp", "embed"),
    }
    if cfg.qk_norm:
        layer["q_norm"] = ("mlp",)
        layer["k_norm"] = ("mlp",)
    axes = {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        axes["head"] = ("embed", "vocab")
    return axes


def balance_loss(expert_tokens, router_prob_sum, rows: int):
    """HF's load_balancing_loss_func from a router's counters over `rows`
    tokens (all layers' together): E * sum_e f_e P_e, f_e the assignments
    to e over the rows (they sum to k), P_e the mean router probability. A
    balanced router gives k."""
    share = expert_tokens.astype(jnp.float32) / rows
    return expert_tokens.shape[-1] * jnp.sum(share * router_prob_sum / rows)


def _hidden(params: Dict, tokens, cfg: MoEConfig):
    """tokens [b, s] -> (final-norm rows [b, s, d], the head, the router's
    counters: `balance_loss`, `router_z`, `expert_tokens` [E] summed over
    layers, `expert_load_max_over_mean`). Both losses are taken over all
    layers' tokens together, as HF's load_balancing_loss_func does."""
    x, head, stats, _ = decoder_hidden(params, tokens, cfg.decoder())
    total = jax.tree.map(lambda *layers: functools.reduce(jnp.add, layers),
                         *stats)
    rows = tokens.size * len(params["layers"])
    counts = total["expert_tokens"]
    counters = {
        "balance_loss": balance_loss(counts, total["router_prob_sum"], rows),
        "router_z": total["router_z_sq_sum"] / rows,
        "expert_tokens": counts,
        "expert_load_max_over_mean":
            jnp.max(counts) / jnp.mean(counts.astype(jnp.float32)),
    }
    return x, head, counters


def _aux_loss(counters: Dict, cfg: MoEConfig):
    return (cfg.aux_loss_weight * counters["balance_loss"]
            + cfg.z_loss_weight * counters["router_z"])


def moe_forward(params: Dict, tokens, cfg: MoEConfig):
    """tokens [b, s] -> (logits [b, s, vocab] fp32, the weighted
    auxiliary loss that moe_loss adds to the cross entropy)."""
    x, head, counters = _hidden(params, tokens, cfg)
    logits = decoder_logits(x, head)
    return logits, _aux_loss(counters, cfg)


def moe_loss_and_counters(params: Dict, batch: Tuple, cfg: MoEConfig):
    """(loss, the router's counters): cross entropy + aux_loss_weight *
    balance_loss + z_loss_weight * router_z."""
    tokens, targets = batch
    x, head, counters = _hidden(params, tokens, cfg)
    loss = cross_entropy(x, head, targets) + _aux_loss(counters, cfg)
    return loss, counters


def moe_loss(params: Dict, batch: Tuple, cfg: MoEConfig):
    return moe_loss_and_counters(params, batch, cfg)[0]


def make_moe_train_step(cfg: MoEConfig, optimizer=None,
                        donate: bool = True, mesh=None, rules=None):
    """The step's metrics carry the router's counters beside `loss`."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: moe_init(key, cfg),
        lambda params, batch: moe_loss_and_counters(params, batch, cfg),
        axes=moe_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules, has_aux=True)
