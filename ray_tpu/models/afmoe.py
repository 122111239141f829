"""Gated grouped-query attention, windowed and position-free layers mixed,
under sandwich norms, with routed experts beside a shared one (Arcee's
Trinity, `model_type` afmoe), TPU-first.

A layer is two branches under FOUR norms with gains of their own: a = x +
N2(Attn(N1 x)); out = a + N4(F(N3 a)) (`ln1`, `post_attention`, `ln2`,
`post_feedforward`: models.decoder._block reads which a layer holds). The
embedding is multiplied by sqrt(d_model) (`mup_enabled`).

    Attn    q = y W_q, [k | v] = y W_kv (grouped-query: `n_heads` over
            `n_kv_heads`), q and k RMS-normed a head over their `head_dim`
            columns (one gain each); by `layer_types`, layer by layer:
            `sliding_attention` rotates q and k (all columns, `rope_theta`)
            and a query sees itself and the `sliding_window` - 1 positions
            before it (models.decoder WINDOWED_ATTENTION: the flash kernels'
            band); `full_attention` is causal over every position and has NO
            positions at all (ATTENTION_NOPE); scores over sqrt(head_dim),
            softmax in float32; out = (concat_h(P v) * sigmoid(y W_g)) W_o,
            W_g [d, heads x head_dim]: one gate a channel (`attn_gate`).
    F       the first `n_dense_layers` a dense SwiGLU of `d_ff`; every later
            layer an expert layer (parallel.moe.held_moe_layer, gated): a
            sigmoid router over all `n_experts`, float32, with a selection
            bias no gradient sees, `experts_per_token` SwiGLU experts of
            `d_expert` a token, their scores over their sum (+ 1e-20:
            `route_norm`) times `routed_scale` (`route_scale`), beside one
            shared SwiGLU expert every token passes, unweighted. No group
            limit.

A final RMSNorm, an untied head, the loss the cross entropy alone.

A chip may hold a share of a layer: `experts_held` = (first, count) of the
`n_experts` the router spans and `vocab_size` rows of the vocabulary. What
the absent experts would add is left out; the shared expert is whole.

The selection biases are state the optimizer does not own, kept as
models.lfm2_moe keeps them: balanced at init on `balance_tokens` seeded ids,
state["held"] [expert layers, n_experts] in a train step, moved
`bias_rounds` rounds on a step's own scores before its layer routes
(parallel.moe.balance_bias; the family's own momentum-and-clamp variant
carries constants config.json does not give).

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router, softmax, the gate's sigmoid and the loss.
Cache (models.generate): {"k", "v"} a layer, `max_len` positions in both
kinds (a windowed layer reads its window of them under a mask).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION_NOPE, WINDOWED_ATTENTION, Decoder,
                      decoder_hidden, decoder_logits, held_gated_experts,
                      keep_kernel_outputs, swiglu_mlp)
from .hybrid import _normal
from .lfm2_moe import _BALANCE_SEQ, _FIXED_POINT_ROUNDS, split_bias, with_bias
from .xing4 import _dense_init, _experts_init

SLIDING, FULL = "sliding_attention", "full_attention"
KINDS = {SLIDING: WINDOWED_ATTENTION, FULL: ATTENTION_NOPE}


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, d_expert =
    moe_intermediate_size, n_experts = num_experts, n_dense_layers =
    num_dense_layers, routed_scale = route_scale)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 512
    n_dense_layers: int = 1         # the leading layers with a dense SwiGLU
    d_ff: int = 1792
    n_experts: int = 32             # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    experts_per_token: int = 4
    d_expert: int = 448
    n_shared_experts: int = 1       # one SwiGLU this many d_expert wide
    routed_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    bias_rounds: int = 48           # of the bias's rule, a training step
    balance_tokens: int = 32768     # 0: the bias starts at zero
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert set(self.layer_types) <= set(KINDS), self.layer_types
        assert self.n_heads % self.n_kv_heads == 0
        assert self.sliding_window >= 1
        assert 0 <= self.n_dense_layers <= self.n_layers
        first, count = self.held
        assert 0 <= first and count > 0 and first + count <= self.n_experts

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def d_shared(self) -> int:
        return self.n_shared_experts * self.d_expert

    def decoder(self, bias_rounds: int = 0) -> Decoder:
        """A windowed, rotated layer or a full one with no positions by
        `layer_types`; GQA from `wq` + `wkv` with a norm a head and a gate
        a channel; the embedding times sqrt(d_model); a channel mixer a
        layer: the dense SwiGLU in the first `n_dense_layers`, the held
        share of the gated experts beside the shared one after them, its
        selection bias as the weights give it or, a training step's, moved
        `bias_rounds` rounds first; under `remat` a block keeps its input
        and its kernels' output and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=self.routed_scale,
            weight_eps=1e-20, bias_rounds=bias_rounds)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            mlp=tuple(swiglu_mlp if i < self.n_dense_layers else experts
                      for i in range(self.n_layers)),
            remat=keep_kernel_outputs if self.remat else None,
            kinds=tuple(KINDS[kind] for kind in self.layer_types),
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            window=self.sliding_window, embed_scale=self.d_model ** 0.5)

    def init(self, key) -> Dict:
        return afmoe_init(key, self)

    @classmethod
    def tiny(cls) -> "AfmoeConfig":
        """One dense layer, then three expert layers that hold experts 2
        to 5 of 16; windowed, windowed, full, windowed under a window of
        16; 3 : 1 GQA: the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=6, n_kv_heads=2,
                   head_dim=16, layer_types=(SLIDING, SLIDING, FULL, SLIDING),
                   sliding_window=16, n_dense_layers=1, d_ff=96,
                   n_experts=16, experts_held=(2, 4), experts_per_token=3,
                   d_expert=24, routed_scale=2.448, bias_rounds=16,
                   balance_tokens=512, max_seq_len=128)

    @classmethod
    def trinity_large_preview(cls) -> "AfmoeConfig":
        """arcee-ai/Trinity-Large-Preview: 60 layers, three windowed
        (4,096) to one full fifteen times, six dense layers and 54 of 4 of
        256 routed experts beside a shared one, every expert held; about
        400 B parameters, 13 B active a token."""
        return cls(vocab_size=200192, d_model=3072, n_heads=48, n_kv_heads=8,
                   head_dim=128, layer_types=(SLIDING, SLIDING, SLIDING,
                                              FULL) * 15,
                   sliding_window=4096, n_dense_layers=6, d_ff=12288,
                   n_experts=256, experts_per_token=4, d_expert=3072,
                   n_shared_experts=1, routed_scale=2.448, rope_theta=10000.0,
                   norm_eps=1e-5, max_seq_len=262144)


def _attention_init(key, cfg: AfmoeConfig) -> Dict:
    kq, kkv, kg, ko = jax.random.split(key, 4)
    d, q_d, std = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.init_std
    return {
        "wq": _normal(kq, (d, q_d), std, cfg.dtype),
        "wkv": _normal(kkv, (d, 2 * cfg.n_kv_heads * cfg.head_dim), std,
                       cfg.dtype),
        "q_head_norm": jnp.ones((cfg.head_dim,), jnp.float32),
        "k_head_norm": jnp.ones((cfg.head_dim,), jnp.float32),
        "attn_gate": _normal(kg, (d, q_d), std, cfg.dtype),
        "wo": _normal(ko, (q_d, d), std, cfg.dtype),
    }


def _weights(key, cfg: AfmoeConfig) -> Dict:
    """Every parameter, the selection biases zero. Every matrix normal at
    `init_std`, the four norms of a block and the final one at 1 (the
    published depth-scaled gains are an initialisation, not a shape); table
    and head apart."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    d = cfg.d_model

    def ones():
        return jnp.ones((d,), jnp.float32)

    def layer(i):
        k_mix, k_ffn = jax.random.split(keys[i + 2])
        ffn = _dense_init if i < cfg.n_dense_layers else _experts_init
        return {"ln1": ones(), **_attention_init(k_mix, cfg),
                "post_attention": ones(), "ln2": ones(), **ffn(k_ffn, cfg),
                "post_feedforward": ones()}

    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), cfg.init_std,
                         cfg.dtype),
        "head": _normal(keys[1], (d, cfg.vocab_size), cfg.init_std,
                        cfg.dtype),
        "lnf": ones(),
        "layers": [layer(i) for i in range(cfg.n_layers)],
    }


@functools.partial(jax.jit, static_argnames="cfg")
def _balanced(params: Dict, key, cfg: AfmoeConfig) -> Dict:
    """`params` with every selection bias at its rule's fixed point on
    `balance_tokens` seeded uniform ids, as models.lfm2_moe._balanced: the
    training forward with each expert layer moving its bias from zero on
    its own scores before it routes."""
    seq = min(cfg.balance_tokens, _BALANCE_SEQ)
    tokens = jax.random.randint(key, (cfg.balance_tokens // seq, seq), 0,
                                cfg.vocab_size)
    dec = cfg.decoder(_FIXED_POINT_ROUNDS)._replace(remat=None)
    stats = decoder_hidden(params, tokens, dec)[2]
    return with_bias(params, [s["router_bias"] for s in stats], cfg)


def afmoe_init(key, cfg: AfmoeConfig) -> Dict:
    """The parameter tree, each expert layer's `router_bias` in it:
    balanced on seeded tokens (the module's docstring), zeros with no
    `balance_tokens`."""
    k_weights, k_tokens = jax.random.split(key)
    params = _weights(k_weights, cfg)
    if cfg.balance_tokens and cfg.n_dense_layers < cfg.n_layers:
        params = _balanced(params, k_tokens, cfg)
    return params


def afmoe_param_axes(cfg: AfmoeConfig) -> Dict:
    mixer = {"wq": ("embed", "mlp"), "wkv": ("embed", "mlp"),
             "q_head_norm": (None,), "k_head_norm": (None,),
             "attn_gate": ("embed", "mlp"), "wo": ("mlp", "embed")}
    dense = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
    experts = {"router": ("embed", None), "router_bias": (None,),
               "expert_gate_up": ("expert", "embed", "mlp"),
               "expert_down": ("expert", "mlp", "embed"),
               "shared_gate_up": ("embed", "mlp"),
               "shared_down": ("mlp", "embed")}
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"),
            "lnf": ("embed",),
            "layers": [{"ln1": ("embed",), **mixer,
                        "post_attention": ("embed",), "ln2": ("embed",),
                        **(dense if i < cfg.n_dense_layers else experts),
                        "post_feedforward": ("embed",)}
                       for i in range(cfg.n_layers)]}


def afmoe_forward(params: Dict, tokens, cfg: AfmoeConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def afmoe_loss_and_counters(params: Dict, batch: Tuple, cfg: AfmoeConfig,
                            held=None):
    """(cross entropy, the routers' counters, a row an expert layer), each
    selection bias moved `cfg.bias_rounds` rounds on the batch's own scores
    before its layer routes. `held`: the biases where `params` comes
    without them (the train step's). The counters are
    models.lfm2_moe.lfm2_moe_loss_and_counters's."""
    if held is not None:
        params = with_bias(params, held, cfg)
    tokens, targets = batch
    x, head, stats, _ = decoder_hidden(params, tokens,
                                       cfg.decoder(cfg.bias_rounds))
    counters = {}
    if stats:
        counters = jax.tree.map(lambda *rows: jnp.stack(rows), *stats)
        counts = counters["expert_tokens"]
        counters.update(
            expert_load_max_over_mean=jnp.max(counts) / jnp.mean(
                counts.astype(jnp.float32)),
            router_bias_abs_max=jnp.max(jnp.abs(counters["router_bias"])))
    return cross_entropy(x, head, targets), counters


def afmoe_loss(params: Dict, batch: Tuple, cfg: AfmoeConfig):
    return afmoe_loss_and_counters(params, batch, cfg)[0]


def make_afmoe_train_step(cfg: AfmoeConfig, optimizer=None,
                          donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step. The
    selection biases are state["held"] [expert layers, n_experts], as
    models.lfm2_moe's. The step's metrics carry the routers' counters
    beside `loss`."""
    from ._training import make_train_step_for

    def init(key):
        params, biases = split_bias(afmoe_init(key, cfg), cfg)
        return params, jnp.stack(biases)

    return make_train_step_for(
        init,
        lambda params, batch, held: afmoe_loss_and_counters(
            params, batch, cfg, held),
        axes=split_bias(afmoe_param_axes(cfg), cfg)[0],
        optimizer=optimizer, donate=donate, mesh=mesh, rules=rules,
        has_aux=True,
        held_update=lambda biases, counters: counters["router_bias"])
