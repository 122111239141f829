"""Kimi-Delta-Attention / latent-attention hybrid decoder with
group-limited routed experts (inclusionAI's Ling-3.0-flash, `model_type`
bailing_hybrid), TPU-first.

A layer is a pre-norm block of two branches, each added to the one
residual stream. The sequence mixer goes by the layer's place: layer i is
multi-head latent attention (models.decoder.latent_attention) where (i + 1)
mod `layer_group_size` = 0 and Kimi Delta Attention (models.decoder.kda
over ops.kda, arXiv:2510.26692) otherwise, five to one published.

    KDA     q | k | v = y W_in, a causal depthwise convolution of
            `conv_taps` taps and silu over all three, q and k L2-normalised
            a head (q over sqrt(head width) besides); beta = sigmoid(y
            W_beta) a head; the log-decay ONE NUMBER A KEY CHANNEL, g =
            `kda_lower_bound` sigmoid(exp(A_log_h) (y W_f + dt_bias)) in
            (bound, 0), float32; a head's state S [K, V] float32: S' =
            Diag(exp(g_t)) S; u = beta (v - S'^T k); S = S' + k u^T; o =
            S^T q; out = (rmsnorm_head(o) * sigmoid(y W_g)) W_o, ONE gate a
            head.
    latent  DeepSeek-V2/V3's, with no query latent (`q_lora_rank` null): q
            = y W_q, a head [q_n | q_r]; [c | k_r] = y W_kva, c normed; a
            head's [k_n | v] = c W_kvb; rotary on the rope columns; out =
            (concat_h(P v) * sigmoid(y W_g)) W_o, the same head-wise gate.

The channel mixer is named per layer: the first `n_dense_layers` run a
dense SwiGLU of `d_ff`, every later one an expert layer
(parallel.moe.held_moe_layer, gated): a sigmoid router over all `n_experts`
with a selection bias no gradient sees, the experts in `n_group` groups of
neighbours of which a token keeps the `topk_group` whose two best biased
scores sum highest (parallel.moe.within_groups), `experts_per_token` SwiGLU
experts of `d_expert` among those, their scores over their sum and times
`routed_scale`, beside a shared SwiGLU expert every token passes. The head
is untied; the loss is the cross entropy (the published prediction module's
loss factor is 0: no module is built).

A chip may hold a share of a layer: `experts_held` = (first, count) of the
`n_experts` the router spans (published: a quarter of one group, 16 of 512)
and `vocab_size` rows of the vocabulary. The group choice is made over all
experts alike on every chip, so the shares add up to the whole layer.

The selection biases are state the optimizer does not own, kept as
models.glm4_moe_lite keeps them: balanced at init on `balance_tokens`
seeded ids, state["held"] [expert layers, n_experts] in a train step, moved
`bias_rounds` rounds on a step's own scores before its layer routes.

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router, g, beta, the rule's state and softmax.
Cache (models.generate): {"conv", "kda"} a KDA layer (the convolution's
last inputs and the heads' states, no growth with the context), {"latent",
"k_rope"} a latent one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (KDA, LATENT_ATTENTION, Decoder, decoder_hidden,
                      decoder_logits, held_gated_experts,
                      keep_kernel_outputs, swiglu_mlp)
from .hybrid import _normal
from .lfm2_moe import _BALANCE_SEQ, _FIXED_POINT_ROUNDS, split_bias, with_bias
from .xing4 import _dense_init, _experts_init


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, d_expert =
    moe_intermediate_size, n_experts = num_experts, n_dense_layers =
    first_k_dense_replace, routed_scale = routed_scaling_factor, conv_taps =
    short_conv_kernel_size, kda_head_dim = head_dim)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8                # of both mixers
    kda_head_dim: int = 128         # a KDA head's key and value width
    conv_taps: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    qk_nope_head_dim: int = 48
    qk_rope_head_dim: int = 16
    v_head_dim: int = 48
    kv_lora_rank: int = 128
    n_layers: int = 6
    layer_group_size: int = 6       # the last of every so many: latent
    n_dense_layers: int = 1         # the leading layers with a dense SwiGLU
    d_ff: int = 1792
    n_experts: int = 64             # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    experts_per_token: int = 4
    n_group: int = 8
    topk_group: int = 4
    d_expert: int = 256
    n_shared_experts: int = 1       # one SwiGLU this many d_expert wide
    routed_scale: float = 2.5
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    init_std: float = 0.02
    bias_rounds: int = 48           # of the bias's rule, a training step
    balance_tokens: int = 32768     # 0: the bias starts at zero
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.qk_rope_head_dim % 2 == 0
        assert self.n_experts % self.n_group == 0
        assert 0 < self.topk_group <= self.n_group
        first, count = self.held
        assert 0 <= first and count > 0 and first + count <= self.n_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def d_shared(self) -> int:
        return self.n_shared_experts * self.d_expert

    @property
    def kinds(self) -> Tuple[str, ...]:
        """A layer's sequence mixer, by its place."""
        return tuple(
            LATENT_ATTENTION if (i + 1) % self.layer_group_size == 0 else KDA
            for i in range(self.n_layers))

    def decoder(self, bias_rounds: int = 0) -> Decoder:
        """KDA and latent attention by the layer's place, their widths
        read off the weights; a channel mixer a layer: the dense SwiGLU in
        the first `n_dense_layers`, the held share of the gated experts
        under the group limit after them, its selection bias as the
        weights give it or, a training step's, moved `bias_rounds` rounds
        first; under `remat` a block keeps its input and what its kernels
        made and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=self.routed_scale,
            weight_eps=1e-20, bias_rounds=bias_rounds, n_group=self.n_group,
            topk_group=self.topk_group)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.qk_head_dim,
            mlp=tuple(swiglu_mlp if i < self.n_dense_layers else experts
                      for i in range(self.n_layers)),
            remat=keep_kernel_outputs if self.remat else None,
            kinds=self.kinds, rope_base=self.rope_theta,
            norm_eps=self.norm_eps, sm_scale=self.qk_head_dim ** -0.5,
            delta_chunk=self.kda_chunk,
            kda_lower_bound=self.kda_lower_bound)

    def init(self, key) -> Dict:
        return bailing_hybrid_init(key, self)

    @classmethod
    def tiny(cls) -> "BailingHybridConfig":
        """A period of three: two KDA layers of two heads of 128 and a
        latent layer of two heads of 12 | 4 and 16 over a 24-wide latent;
        one dense layer, then two expert layers holding experts 2 to 5 of
        16 in 4 groups of which a token keeps 2: the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=2, kda_head_dim=128,
                   kda_chunk=64, qk_nope_head_dim=12, qk_rope_head_dim=4,
                   v_head_dim=16, kv_lora_rank=24, n_layers=3,
                   layer_group_size=3, n_dense_layers=1, d_ff=96,
                   n_experts=16, experts_held=(2, 4), experts_per_token=3,
                   n_group=4, topk_group=2, d_expert=24, routed_scale=2.5,
                   bias_rounds=16, balance_tokens=512, max_seq_len=256)

    @classmethod
    def ling_3_0_flash(cls) -> "BailingHybridConfig":
        """inclusionAI/Ling-3.0-flash: 42 layers, five KDA to one latent
        layer seven times, two dense layers and 40 of 512 routed experts
        in 8 groups and a shared one, every expert held; about 125 B
        parameters, 5.5 B active a token."""
        return cls(vocab_size=157184, d_model=2560, n_heads=32,
                   kda_head_dim=128, conv_taps=4, kda_lower_bound=-5.0,
                   qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                   kv_lora_rank=512, n_layers=42, layer_group_size=6,
                   n_dense_layers=2, d_ff=6144, n_experts=512,
                   experts_per_token=8, n_group=8, topk_group=4,
                   d_expert=768, n_shared_experts=1, routed_scale=2.5,
                   rope_theta=6000000.0, norm_eps=1e-6, max_seq_len=262144)


def _kda_init(key, cfg: BailingHybridConfig) -> Dict:
    """A KDA mixer's weights. A_log at 0 (a rate of 1) and dt_bias at 0:
    the gate starts at half its bound, -2.5 a step, in every channel."""
    k_in, k_conv, k_f, k_beta, k_gate, k_out = jax.random.split(key, 6)
    d, H, K, std = cfg.d_model, cfg.n_heads, cfg.kda_head_dim, cfg.init_std
    return {
        "kda_in": _normal(k_in, (d, 3 * H * K), std, cfg.dtype),   # q | k | v
        "conv_w": _normal(k_conv, (3 * H * K, cfg.conv_taps),
                          cfg.conv_taps ** -0.5, jnp.float32),
        "kda_f": _normal(k_f, (d, H * K), std, cfg.dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "dt_bias": jnp.zeros((H * K,), jnp.float32),
        "kda_beta": _normal(k_beta, (d, H), std, cfg.dtype),
        "head_gate": _normal(k_gate, (d, H), std, cfg.dtype),
        "kda_norm": jnp.ones((K,), jnp.float32),
        "kda_out": _normal(k_out, (H * K, d), std, cfg.dtype),
    }


def _latent_init(key, cfg: BailingHybridConfig) -> Dict:
    """A latent mixer's weights: no query latent, one gate a head."""
    kq, ka, kb, kg, ko = jax.random.split(key, 5)
    d, h, c, std = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.init_std
    return {
        # a head's no-rope | rope columns
        "wq": _normal(kq, (d, h * cfg.qk_head_dim), std, cfg.dtype),
        # the latent | the rope key every head shares
        "w_kva": _normal(ka, (d, c + cfg.qk_rope_head_dim), std, cfg.dtype),
        "latent_norm": jnp.ones((c,), jnp.float32),
        # a head's no-rope key | value columns
        "w_kvb": _normal(kb, (c, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                         std, cfg.dtype),
        "head_gate": _normal(kg, (d, h), std, cfg.dtype),
        "wo": _normal(ko, (h * cfg.v_head_dim, d), std, cfg.dtype),
    }


def _weights(key, cfg: BailingHybridConfig) -> Dict:
    """Every parameter, the selection biases zero. Every matrix normal at
    `init_std`, norms 1; table and head apart."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    d = cfg.d_model

    def layer(i, kind):
        k_mix, k_ffn = jax.random.split(keys[i + 2])
        mixer = _latent_init if kind == LATENT_ATTENTION else _kda_init
        ffn = _dense_init if i < cfg.n_dense_layers else _experts_init
        return {"ln1": jnp.ones((d,), jnp.float32), **mixer(k_mix, cfg),
                "ln2": jnp.ones((d,), jnp.float32), **ffn(k_ffn, cfg)}

    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), cfg.init_std,
                         cfg.dtype),
        "head": _normal(keys[1], (d, cfg.vocab_size), cfg.init_std,
                        cfg.dtype),
        "lnf": jnp.ones((d,), jnp.float32),
        "layers": [layer(i, kind) for i, kind in enumerate(cfg.kinds)],
    }


@functools.partial(jax.jit, static_argnames="cfg")
def _balanced(params: Dict, key, cfg: BailingHybridConfig) -> Dict:
    """`params` with every selection bias at its rule's fixed point on
    `balance_tokens` seeded uniform ids, as models.glm4_moe_lite._balanced:
    the training forward with each expert layer moving its bias from zero
    on its own scores, under the group limit, before it routes."""
    seq = min(cfg.balance_tokens, _BALANCE_SEQ)
    tokens = jax.random.randint(key, (cfg.balance_tokens // seq, seq), 0,
                                cfg.vocab_size)
    dec = cfg.decoder(_FIXED_POINT_ROUNDS)._replace(remat=None)
    stats = decoder_hidden(params, tokens, dec)[2]
    return with_bias(params, [s["router_bias"] for s in stats
                              if "router_bias" in s], cfg)


def bailing_hybrid_init(key, cfg: BailingHybridConfig) -> Dict:
    """The parameter tree, each expert layer's `router_bias` in it:
    balanced on seeded tokens (the module's docstring), zeros with no
    `balance_tokens`."""
    k_weights, k_tokens = jax.random.split(key)
    params = _weights(k_weights, cfg)
    if cfg.balance_tokens and cfg.n_dense_layers < cfg.n_layers:
        params = _balanced(params, k_tokens, cfg)
    return params


def bailing_hybrid_param_axes(cfg: BailingHybridConfig) -> Dict:
    mixer = {
        KDA: {"kda_in": ("embed", "mlp"), "conv_w": ("mlp", None),
              "kda_f": ("embed", "mlp"), "A_log": (None,),
              "dt_bias": ("mlp",), "kda_beta": ("embed", None),
              "head_gate": ("embed", None), "kda_norm": (None,),
              "kda_out": ("mlp", "embed")},
        LATENT_ATTENTION: {"wq": ("embed", "mlp"), "w_kva": ("embed", None),
                           "latent_norm": (None,), "w_kvb": (None, "mlp"),
                           "head_gate": ("embed", None),
                           "wo": ("mlp", "embed")}}
    dense = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
    experts = {"router": ("embed", None), "router_bias": (None,),
               "expert_gate_up": ("expert", "embed", "mlp"),
               "expert_down": ("expert", "mlp", "embed"),
               "shared_gate_up": ("embed", "mlp"),
               "shared_down": ("mlp", "embed")}
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"),
            "lnf": ("embed",),
            "layers": [{"ln1": ("embed",), **mixer[kind], "ln2": ("embed",),
                        **(dense if i < cfg.n_dense_layers else experts)}
                       for i, kind in enumerate(cfg.kinds)]}


def bailing_hybrid_forward(params: Dict, tokens, cfg: BailingHybridConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def bailing_hybrid_loss_and_counters(params: Dict, batch: Tuple,
                                     cfg: BailingHybridConfig, held=None):
    """(cross entropy, the step's counters), each selection bias moved
    `cfg.bias_rounds` rounds on the batch's own scores, under the group
    limit, before its layer routes. `held`: the biases where `params`
    comes without them (the train step's). Counters: the routers' as
    models.lfm2_moe's, a row an expert layer (`router_bias`,
    `expert_tokens`, `router_prob_sum` [expert layers, n_experts],
    `expert_rows_held`, `expert_passes` [expert layers],
    `expert_load_max_over_mean`, `router_bias_abs_max`), with
    `expert_groups_kept` [expert layers, n_group], the tokens that kept
    each group (topk_group x T a layer); and `kda_log_decay_min` [KDA
    layers], the smallest log-decay of the step, never under the bound."""
    if held is not None:
        params = with_bias(params, held, cfg)
    tokens, targets = batch
    x, head, stats, _ = decoder_hidden(params, tokens,
                                       cfg.decoder(cfg.bias_rounds))
    counters = {}
    decays = [s["kda_log_decay_min"] for s in stats
              if "kda_log_decay_min" in s]
    if decays:
        counters["kda_log_decay_min"] = jnp.stack(decays)
    routed = [{k: v for k, v in s.items() if k != "kda_log_decay_min"}
              for s in stats if "expert_tokens" in s]
    if routed:
        counters.update(jax.tree.map(lambda *rows: jnp.stack(rows), *routed))
        counts = counters["expert_tokens"]
        counters.update(
            expert_load_max_over_mean=jnp.max(counts) / jnp.mean(
                counts.astype(jnp.float32)),
            router_bias_abs_max=jnp.max(jnp.abs(counters["router_bias"])))
    return cross_entropy(x, head, targets), counters


def bailing_hybrid_loss(params: Dict, batch: Tuple,
                        cfg: BailingHybridConfig):
    return bailing_hybrid_loss_and_counters(params, batch, cfg)[0]


def make_bailing_hybrid_train_step(cfg: BailingHybridConfig, optimizer=None,
                                   donate: bool = True, mesh=None,
                                   rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step. The
    selection biases are state["held"] [expert layers, n_experts], as
    models.glm4_moe_lite's. The step's metrics carry the counters beside
    `loss`."""
    from ._training import make_train_step_for

    def init(key):
        params, biases = split_bias(bailing_hybrid_init(key, cfg), cfg)
        return params, jnp.stack(biases)

    return make_train_step_for(
        init,
        lambda params, batch, held: bailing_hybrid_loss_and_counters(
            params, batch, cfg, held),
        axes=split_bias(bailing_hybrid_param_axes(cfg), cfg)[0],
        optimizer=optimizer, donate=donate, mesh=mesh, rules=rules,
        has_aux=True,
        held_update=lambda biases, counters: counters["router_bias"])
