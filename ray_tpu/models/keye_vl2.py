"""Sparse-attention / routed-expert decoder (the language decoder of
Kwai-Keye's Keye-VL-2.0-30B-A3B, `model_type` KeyeVL2: Qwen3-MoE's layer
with DeepSeek sparse attention in the place of full attention), TPU-first.

Every layer is a pre-norm block of two branches on one residual stream, x +
mixer(rmsnorm(x)) then x + experts(rmsnorm(x)), and all are alike:

* the sequence mixer (models.decoder.sparse_attention) is grouped-query
  attention, `n_heads` query heads over `n_kv_heads` of keys and values, q
  and k normed a head (one [head_dim] weight each), rotary over all of a
  head's columns at `rope_theta`, over the `index_topk` keys a query's own
  LIGHTNING INDEXER picks among those at or before it: `index_heads` small
  heads of `index_head_dim` against one key head, I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s]) (ops/sparse_index.py). The indexer reads the
  normed input detached and is trained beside the model by a loss of its
  own, L_I, the KL from the heads' mean attention probability over the
  selected keys to the softmax of its scores there: the cross entropy
  reaches none of its four weights, and L_I reaches nothing else.
* the channel mixer is the expert layer (parallel.moe.held_moe_layer,
  gated, `softmax`): p = softmax(y W_r) over all `n_experts` in float32, no
  bias of any kind, the top `experts_per_token` of p weighted by p over
  their own sum (`norm_topk_prob`), SwiGLU experts of `d_expert`, no shared
  expert.

The head is untied. The training loss is

    L = CE + router_aux_loss_coef x balance + index_loss_weight x sum over
        the layers of L_I

balance = n_experts x sum_e f_e P_e over all layers' tokens together
(models.moe.balance_loss: HF's load_balancing_loss_func).

The multimodal rotary embedding (`rope_scaling.mrope_section`: the
frequency pairs divided among a temporal, a height and a width position)
is plain rotary on text, where the three positions are one; no image
position runs here, and the vision tower is not in this module.

A chip may hold a share of a layer (expert parallelism without its
exchange): `experts_held` = (first, count) of the `n_experts` the router
spans, and `vocab_size` rows of the vocabulary. What the absent experts
would add is left out. WHICH experts a chip holds is a placement
(parallel.moe.place_experts): a deployment relabels the router's columns so
that its chip's experts come first, which this module never sees.

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router, softmaxes, index scores and L_I. Cache: a
layer {"k" | "v": [batch, n_kv_heads, max_len, head_dim], "k_index":
[batch, max_len, index_head_dim]}.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (SPARSE_ATTENTION, Decoder, decoder_hidden,
                      decoder_logits, held_gated_experts,
                      keep_kernel_outputs)
from .hybrid import _normal
from .moe import balance_loss


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_expert = moe_intermediate_size, n_experts
    = num_experts; index_* = sa_config's indexer_num_heads,
    indexer_head_dim and topk)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    index_heads: int = 4
    index_head_dim: int = 32
    index_topk: int = 512
    n_experts: int = 64                 # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    experts_per_token: int = 4
    d_expert: int = 128
    router_aux_loss_coef: float = 0.001
    index_loss_weight: float = 1.0
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-6
    init_std: float = 0.02
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert self.n_heads % self.n_kv_heads == 0
        assert self.index_topk >= 1
        first, count = self.held
        assert 0 <= first and count > 0 and first + count <= self.n_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def decoder(self) -> Decoder:
        """Sparse attention in every layer, GQA from `wq` + `wkv` with a
        norm a head; the held share of the gated experts under the softmax
        router in every layer; under `remat` a block keeps what its kernels
        and its selection made and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=1.0, weight_eps=0.0,
            softmax=True)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, mlp=(experts,) * self.n_layers,
            remat=keep_kernel_outputs if self.remat else None,
            kinds=(SPARSE_ATTENTION,) * self.n_layers,
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            sparse_topk=self.index_topk)

    def init(self, key) -> Dict:
        return keye_vl2_init(key, self)

    @classmethod
    def tiny(cls) -> "KeyeVL2Config":
        """Three layers of 4 heads of 16 over 2, an indexer of 2 heads of 8
        that names 48 keys a query; experts 8 to 15 of 32 held, 3 a token:
        the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                   n_kv_heads=2, head_dim=16, index_heads=2,
                   index_head_dim=8, index_topk=48, n_experts=32,
                   experts_held=(8, 8), experts_per_token=3, d_expert=24,
                   max_seq_len=256)

    @classmethod
    def keye_vl_2_30b_a3b(cls) -> "KeyeVL2Config":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B's language decoder: 48 layers of
        32 heads of 128 over 4, an indexer of 16 heads of 64 naming 2,048
        keys a query, 128 experts of 768, 8 a token; 30 B parameters, about
        3 B active a token."""
        return cls(vocab_size=151936, d_model=2048, n_layers=48, n_heads=32,
                   n_kv_heads=4, head_dim=128, index_heads=16,
                   index_head_dim=64, index_topk=2048, n_experts=128,
                   experts_per_token=8, d_expert=768, rope_theta=10000000.0,
                   norm_eps=1e-6, max_seq_len=262144)


# The indexer's own leaves of a layer: what L_I reaches, and nothing else
# does.
INDEXER_LEAVES = ("index_wq", "index_wk", "index_k_norm", "index_k_norm_b",
                  "index_ww")


def _layer_init(key, cfg: KeyeVL2Config) -> Dict:
    kq, kkv, ko, kiq, kik, kiw, kr, k1, k2 = jax.random.split(key, 9)
    d, hd, std = cfg.d_model, cfg.head_dim, cfg.init_std
    wide, f, held = cfg.n_heads * hd, cfg.d_expert, cfg.held[1]
    H, D = cfg.index_heads, cfg.index_head_dim
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "wq": _normal(kq, (d, wide), std, cfg.dtype),
        "wkv": _normal(kkv, (d, 2 * cfg.n_kv_heads * hd), std, cfg.dtype),
        "q_head_norm": jnp.ones((hd,), jnp.float32),
        "k_head_norm": jnp.ones((hd,), jnp.float32),
        "wo": _normal(ko, (wide, d), std, cfg.dtype),
        "index_wq": _normal(kiq, (d, H * D), std, cfg.dtype),
        "index_wk": _normal(kik, (d, D), std, cfg.dtype),
        "index_k_norm": jnp.ones((D,), jnp.float32),
        "index_k_norm_b": jnp.zeros((D,), jnp.float32),
        "index_ww": _normal(kiw, (d, H), std, cfg.dtype),
        "ln2": jnp.ones((d,), jnp.float32),
        # float32: routing decisions are precision-sensitive (models/moe.py)
        "router": jax.random.normal(kr, (d, cfg.n_experts)) * std,
        # an expert's gate and up matrices side by side, the gate first
        "expert_gate_up": _normal(k1, (held, d, 2 * f), std, cfg.dtype),
        "expert_down": _normal(k2, (held, f, d), std, cfg.dtype),
    }


def keye_vl2_init(key, cfg: KeyeVL2Config) -> Dict:
    """Every matrix normal at `init_std`, norms at one (the indexer's key
    norm's bias at zero); table and head apart."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    d = cfg.d_model
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), cfg.init_std,
                         cfg.dtype),
        "head": _normal(keys[1], (d, cfg.vocab_size), cfg.init_std,
                        cfg.dtype),
        "lnf": jnp.ones((d,), jnp.float32),
        "layers": [_layer_init(keys[i + 2], cfg)
                   for i in range(cfg.n_layers)],
    }


def keye_vl2_param_axes(cfg: KeyeVL2Config) -> Dict:
    layer = {"ln1": ("embed",), "wq": ("embed", "mlp"),
             "wkv": ("embed", "mlp"), "q_head_norm": (None,),
             "k_head_norm": (None,), "wo": ("mlp", "embed"),
             "index_wq": ("embed", None), "index_wk": ("embed", None),
             "index_k_norm": (None,), "index_k_norm_b": (None,),
             "index_ww": ("embed", None), "ln2": ("embed",),
             "router": ("embed", None),
             "expert_gate_up": ("expert", "embed", "mlp"),
             "expert_down": ("expert", "mlp", "embed")}
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"),
            "lnf": ("embed",),
            "layers": [dict(layer) for _ in range(cfg.n_layers)]}


def keye_vl2_forward(params: Dict, tokens, cfg: KeyeVL2Config):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def keye_vl2_loss_and_counters(params: Dict, batch: Tuple,
                               cfg: KeyeVL2Config):
    """(the training loss L of the module's docstring, the step's
    counters): `loss_ce`, the cross entropy; `balance_loss`, before its
    coefficient; `index_loss` [layers], each layer's L_I, and
    `selected_keys_mean`, the keys a query saw (the layers' mean); the
    routers' a row a layer: `expert_tokens`, `router_prob_sum` [layers,
    n_experts], `expert_rows_held`, `expert_passes` [layers]; and
    `expert_load_max_over_mean`. There is no selection bias:
    `router_bias_abs_max` is the zero the other held-share families' tools
    read (chipbench/step_counters.py)."""
    tokens, targets = batch
    x, head, stats, _ = decoder_hidden(params, tokens, cfg.decoder())
    counters = jax.tree.map(lambda *rows: jnp.stack(rows), *stats)
    counts = counters["expert_tokens"]
    balance = balance_loss(
        jnp.sum(counts, axis=0), jnp.sum(counters["router_prob_sum"], axis=0),
        tokens.size * cfg.n_layers)
    loss_ce = cross_entropy(x, head, targets)
    counters.update(
        loss_ce=loss_ce, balance_loss=balance,
        selected_keys_mean=jnp.mean(counters["selected_keys_mean"]),
        expert_load_max_over_mean=jnp.max(counts) / jnp.mean(
            counts.astype(jnp.float32)),
        router_bias_abs_max=jnp.zeros((), jnp.float32))
    loss = loss_ce + cfg.router_aux_loss_coef * balance \
        + cfg.index_loss_weight * jnp.sum(counters["index_loss"])
    return loss, counters


def keye_vl2_loss(params: Dict, batch: Tuple, cfg: KeyeVL2Config):
    return keye_vl2_loss_and_counters(params, batch, cfg)[0]


def make_keye_vl2_train_step(cfg: KeyeVL2Config, optimizer=None,
                             donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step; the
    step's metrics carry the counters, `loss_ce`, `balance_loss` and
    `index_loss` among them, beside `loss`."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: keye_vl2_init(key, cfg),
        lambda params, batch: keye_vl2_loss_and_counters(params, batch, cfg),
        axes=keye_vl2_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules, has_aux=True)
