"""Hyper-connected latent-attention / routed-expert decoder (XingChen-AGI's
Xing4.0-29B-A4B: DeepSeek-V3's attention, router and experts round a
residual path of several streams), TPU-first.

The stack carries `hc_mult` residual streams X (n arrays [b, L, d]), each
the embedding at the start, summed before the final norm. A layer is two
branches, attention then feed-forward, each joined to the streams by
manifold-constrained hyper-connections (models.decoder.hyper_connection):
the branch reads one learned, per-token mix of the streams, sum_i H_pre[i]
X[i], and the block returns H_res X + H_post (x) branch(rmsnorm(mix)), H_res
a per-token doubly stochastic n x n matrix (twenty Sinkhorn-Knopp rounds
over an exp). Every sequence mixer is multi-head latent attention
(models.decoder.latent_attention): queries through a `q_lora_rank`-wide
normed latent, ONE `kv_lora_rank`-wide latent and ONE
`qk_rope_head_dim`-wide rotated key a token, a head's no-rope key and its
value made from the normed latent by a second matrix, rotary at YaRN's
blended frequencies, scores over sqrt(qk_nope_head_dim + qk_rope_head_dim)
times YaRN's temperature squared. The channel mixer is named per layer:
the first `n_dense_layers` run a dense SwiGLU of `d_ff`, every later one
an expert layer (parallel.moe.held_moe_layer, gated): a sigmoid router
over all `n_experts` with a selection bias that no gradient sees,
`experts_per_token` SwiGLU experts of `d_expert` a token, their scores
over their sum and times `routed_scale`, beside a shared SwiGLU expert
`n_shared_experts` x `d_expert` wide that every token passes. The head is
untied; the loss is the cross entropy alone (`noaux_tc`: no balance loss).
`Xing4Config.xing4_29b_a4b()` is the published config.json; its
multi-token-prediction module is not built.

A chip may hold a share of a layer (expert parallelism without its
exchange): `experts_held` = (first, count) of the `n_experts` the router
spans, and `vocab_size` rows of the vocabulary. What the absent experts
would add is left out; the shared expert is whole on every chip.

The selection bias is state the optimizer does not own, kept as
models.lfm2_moe keeps it and for models.nemotron_h's reasons: the init
returns it inside the tree at its rule's fixed point on `balance_tokens`
seeded uniform ids, the train step keeps it in state["held"], and it
moves `bias_rounds` rounds on a step's own scores BEFORE the step routes.

A hyper-connection starts as a pre-norm block that reads the streams' sum
and writes to every stream: the three gains 0.01, the static parts zero
(H_pre = 1/2, H_post = 1) but H_res's, `hc_res_init` on its diagonal
(within 1e-3 of the identity), phi normal at the model's std.

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router, softmax and hyper-connection coefficients.
Cache: {"latent", "k_rope"} a layer, 576 values a token at the published
sizes, not per-head keys and values; the streams are no state (the rule
is a token's own).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.layers import yarn_inv_freq, yarn_mscale
from ..ops.loss import cross_entropy
from .decoder import (LATENT_ATTENTION, Decoder, HyperConnections,
                      decoder_hidden, decoder_logits, held_gated_experts,
                      keep_kernel_outputs, swiglu_mlp)
from .hybrid import _normal
# The selection biases sit in the expert layers, those after the first
# `n_dense_layers`, as LFM2's do: the same functions take them out of a
# tree and put them back. They read a config's `n_layers` and
# `n_dense_layers`.
from .lfm2_moe import (_BALANCE_SEQ, _FIXED_POINT_ROUNDS,  # noqa: F401
                       split_bias, with_bias)

# The hyper-connections' two sets of weights a layer, by the branch.
HC_BRANCHES = ("hc_mixer", "hc_mlp")


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, d_expert =
    moe_intermediate_size, n_experts = n_routed_experts, n_dense_layers =
    first_k_dense_replace, routed_scale = routed_scaling_factor; the
    `rope_scaling` group's keys behind `yarn_`)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64
    q_lora_rank: int = 96
    kv_lora_rank: int = 128
    n_layers: int = 4
    n_dense_layers: int = 1         # the leading layers with a dense SwiGLU
    d_ff: int = 1792
    n_experts: int = 16             # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    experts_per_token: int = 4
    d_expert: int = 256
    n_shared_experts: int = 1       # one SwiGLU this many d_expert wide
    routed_scale: float = 2.0
    rope_theta: float = 10000.0
    yarn_factor: float = 64.0
    yarn_original_max_seq_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 1.0    # = mscale: cos and sin are not scaled
    norm_eps: float = 1e-6
    hc_mult: int = 4                # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    hc_alpha_init: float = 0.01     # the program's start, no checkpoint's
    hc_res_init: float = 8.0
    init_std: float = 0.02
    bias_rounds: int = 48           # of the bias's rule, a training step
    balance_tokens: int = 32768     # 0: the bias starts at zero
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.qk_rope_head_dim % 2 == 0
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
    def sm_scale(self) -> float:
        """1 / sqrt(a head's q width) times YaRN's temperature squared."""
        return self.qk_head_dim ** -0.5 * yarn_mscale(
            self.yarn_factor, self.yarn_mscale_all_dim) ** 2

    def decoder(self, bias_rounds: int = 0) -> Decoder:
        """Latent attention in every layer, its widths read off the
        weights, at YaRN's frequencies and scale; a channel mixer a layer:
        the dense SwiGLU in the first `n_dense_layers`, the held share of
        the gated experts with their shared expert after them, its
        selection bias as the weights give it or, a training step's, moved
        `bias_rounds` rounds first; `hc_mult` streams joined by each
        layer's hyper-connections; under `remat` a block keeps its streams,
        the latent and what its kernels made and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=self.routed_scale,
            weight_eps=1e-20, bias_rounds=bias_rounds)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.qk_head_dim,
            mlp=tuple(swiglu_mlp if i < self.n_dense_layers else experts
                      for i in range(self.n_layers)),
            remat=keep_kernel_outputs if self.remat else None,
            kinds=(LATENT_ATTENTION,) * self.n_layers,
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            sm_scale=self.sm_scale,
            rope_inv_freq=yarn_inv_freq(
                self.qk_rope_head_dim, self.rope_theta, self.yarn_factor,
                self.yarn_original_max_seq_len, self.yarn_beta_fast,
                self.yarn_beta_slow),
            hyper=HyperConnections(self.hc_mult, self.hc_sinkhorn_iters,
                                   self.hc_eps, tuple(self.hc_res_clamp)))

    def init(self, key) -> Dict:
        return xing4_init(key, self)

    @classmethod
    def tiny(cls) -> "Xing4Config":
        """One dense layer, then two expert layers that hold experts 2
        to 5 of 8 beside a shared expert; four heads of 16 | 8 query and
        key columns and 12 value columns over a 24-wide latent, queries
        through a 40-wide one; three streams; a context scaled by 4 over
        64 positions: the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=4, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=12, q_lora_rank=40,
                   kv_lora_rank=24, n_layers=3, n_dense_layers=1, d_ff=96,
                   n_experts=8, experts_held=(2, 4), experts_per_token=3,
                   d_expert=24, n_shared_experts=1, routed_scale=2.0,
                   yarn_factor=4.0, yarn_original_max_seq_len=64,
                   yarn_beta_fast=4.0, hc_mult=3, bias_rounds=16,
                   balance_tokens=512, max_seq_len=256)

    @classmethod
    def xing4_29b_a4b(cls) -> "Xing4Config":
        """XingChen-AGI/Xing4.0-29B-A4B: 40 layers, two dense and 38 expert
        layers of 64 routed experts and a shared one, every expert held,
        four streams; 29 B parameters, about 4 B active a token."""
        return cls(vocab_size=131072, d_model=3584, n_heads=32,
                   qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                   q_lora_rank=768, kv_lora_rank=512, n_layers=40,
                   n_dense_layers=2, d_ff=9216, n_experts=64,
                   experts_per_token=4, d_expert=1024, n_shared_experts=1,
                   routed_scale=2.0, rope_theta=10000.0, yarn_factor=64.0,
                   yarn_original_max_seq_len=4096, norm_eps=1e-6, hc_mult=4,
                   max_seq_len=262144)


def _hyper_init(key, cfg: Xing4Config) -> Dict:
    """One branch's hyper-connection at the start the module's docstring
    gives: phi [n d, 2 n + n^2] in the model's dtype, the gains and the
    static part float32."""
    n = cfg.hc_mult
    return {
        "phi": _normal(key, (n * cfg.d_model, 2 * n + n * n), cfg.init_std,
                       cfg.dtype),
        "alpha": jnp.full((3,), cfg.hc_alpha_init, jnp.float32),
        "b": jnp.concatenate([
            jnp.zeros((2 * n,), jnp.float32),
            (cfg.hc_res_init * jnp.eye(n, dtype=jnp.float32)).reshape(-1)]),
    }


def _attention_init(key, cfg: Xing4Config) -> Dict:
    kqa, kqb, ka, kb, ko = jax.random.split(key, 5)
    d, h, c, std = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.init_std
    return {
        "w_qa": _normal(kqa, (d, cfg.q_lora_rank), std, cfg.dtype),
        "q_latent_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        # a head's no-rope | rope columns
        "w_qb": _normal(kqb, (cfg.q_lora_rank, h * cfg.qk_head_dim), std,
                        cfg.dtype),
        # the latent | the rope key every head shares
        "w_kva": _normal(ka, (d, c + cfg.qk_rope_head_dim), std, cfg.dtype),
        "latent_norm": jnp.ones((c,), jnp.float32),
        # a head's no-rope key | value columns
        "w_kvb": _normal(kb, (c, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                         std, cfg.dtype),
        "wo": _normal(ko, (h * cfg.v_head_dim, d), std, cfg.dtype),
    }


def _dense_init(key, cfg: Xing4Config) -> Dict:
    kg, ku, kd = jax.random.split(key, 3)
    d, f, std = cfg.d_model, cfg.d_ff, cfg.init_std
    return {"w_gate": _normal(kg, (d, f), std, cfg.dtype),
            "w_up": _normal(ku, (d, f), std, cfg.dtype),
            "w_down": _normal(kd, (f, d), std, cfg.dtype)}


def _experts_init(key, cfg: Xing4Config) -> Dict:
    kr, k1, k2, k3, k4 = jax.random.split(key, 5)
    d, f, fs, held = cfg.d_model, cfg.d_expert, cfg.d_shared, cfg.held[1]
    std = cfg.init_std
    return {
        # float32: routing decisions are precision-sensitive (models/moe.py)
        "router": jax.random.normal(kr, (d, cfg.n_experts)) * std,
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        # an expert's gate and up matrices side by side, the gate first
        "expert_gate_up": _normal(k1, (held, d, 2 * f), std, cfg.dtype),
        "expert_down": _normal(k2, (held, f, d), std, cfg.dtype),
        "shared_gate_up": _normal(k3, (d, 2 * fs), std, cfg.dtype),
        "shared_down": _normal(k4, (fs, d), std, cfg.dtype),
    }


def _weights(key, cfg: Xing4Config) -> Dict:
    """Every parameter, the selection biases zero. Every matrix normal at
    `init_std` (the family code's), norms 1; table and head apart."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    d = cfg.d_model

    def layer(i):
        k_mix, k_ffn, k_hc_mix, k_hc_mlp = jax.random.split(keys[i + 2], 4)
        ffn = _dense_init(k_ffn, cfg) if i < cfg.n_dense_layers \
            else _experts_init(k_ffn, cfg)
        return {"ln1": jnp.ones((d,), jnp.float32),
                **_attention_init(k_mix, cfg),
                "ln2": jnp.ones((d,), jnp.float32), **ffn,
                "hc_mixer": _hyper_init(k_hc_mix, cfg),
                "hc_mlp": _hyper_init(k_hc_mlp, cfg)}

    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), cfg.init_std,
                         cfg.dtype),
        "head": _normal(keys[1], (d, cfg.vocab_size), cfg.init_std,
                        cfg.dtype),
        "lnf": jnp.ones((d,), jnp.float32),
        "layers": [layer(i) for i in range(cfg.n_layers)],
    }


def _routers(stats) -> list:
    """The expert layers' entries of decoder_hidden's `stats` (every layer
    of this family gives one: its hyper-connections' counters)."""
    return [s for s in stats if "router_bias" in s]


@functools.partial(jax.jit, static_argnames="cfg")
def _balanced(params: Dict, key, cfg: Xing4Config) -> Dict:
    """`params` with every expert layer's selection bias at its rule's
    fixed point on `balance_tokens` seeded uniform ids, as
    models.lfm2_moe._balanced: the training forward with each expert layer
    moving its bias from zero on its own scores before it routes."""
    seq = min(cfg.balance_tokens, _BALANCE_SEQ)
    tokens = jax.random.randint(key, (cfg.balance_tokens // seq, seq), 0,
                                cfg.vocab_size)
    dec = cfg.decoder(_FIXED_POINT_ROUNDS)._replace(remat=None)
    stats = decoder_hidden(params, tokens, dec)[2]
    return with_bias(params, [s["router_bias"] for s in _routers(stats)], cfg)


def xing4_init(key, cfg: Xing4Config) -> Dict:
    """The parameter tree, each expert layer's `router_bias` in it:
    balanced on seeded tokens (the module's docstring), zeros with no
    `balance_tokens`."""
    k_weights, k_tokens = jax.random.split(key)
    params = _weights(k_weights, cfg)
    if cfg.balance_tokens and cfg.n_dense_layers < cfg.n_layers:
        params = _balanced(params, k_tokens, cfg)
    return params


def xing4_param_axes(cfg: Xing4Config) -> Dict:
    attention = {"w_qa": ("embed", None), "q_latent_norm": (None,),
                 "w_qb": (None, "mlp"), "w_kva": ("embed", None),
                 "latent_norm": (None,), "w_kvb": (None, "mlp"),
                 "wo": ("mlp", "embed")}
    dense = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
    experts = {"router": ("embed", None), "router_bias": (None,),
               "expert_gate_up": ("expert", "embed", "mlp"),
               "expert_down": ("expert", "mlp", "embed"),
               "shared_gate_up": ("embed", "mlp"),
               "shared_down": ("mlp", "embed")}
    hyper = {"phi": (None, None), "alpha": (None,), "b": (None,)}
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"),
            "lnf": ("embed",),
            "layers": [{"ln1": ("embed",), **attention, "ln2": ("embed",),
                        **(dense if i < cfg.n_dense_layers else experts),
                        **{branch: dict(hyper) for branch in HC_BRANCHES}}
                       for i in range(cfg.n_layers)]}


def xing4_forward(params: Dict, tokens, cfg: Xing4Config):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def xing4_loss_and_counters(params: Dict, batch: Tuple, cfg: Xing4Config,
                            held=None):
    """(cross entropy, the step's counters), each selection bias moved
    `cfg.bias_rounds` rounds on the batch's own scores before its layer
    routes. `held`: the biases where `params` comes without them (the train
    step's). Counters, the routers' as models.lfm2_moe's, a row an expert
    layer: `router_bias`, `expert_tokens`, `router_prob_sum` [expert
    layers, n_experts], `expert_rows_held`, `expert_passes` [expert
    layers], `expert_load_max_over_mean`, `router_bias_abs_max`; and the
    hyper-connections', [layers, 2] (a layer's attention branch, then its
    feed-forward one): `hc_res_offdiag_max`, the largest off-diagonal entry
    of any token's H_res (the streams still mix), and `hc_res_col_err_max`,
    the largest |column sum - 1| after the Sinkhorn rounds (the matrix is
    still on its manifold; the last half-round leaves the rows exact)."""
    if held is not None:
        params = with_bias(params, held, cfg)
    tokens, targets = batch
    x, head, stats, _ = decoder_hidden(params, tokens,
                                       cfg.decoder(cfg.bias_rounds))

    def stacked(rows, names):
        # (a hyper-connection's counters come one scalar a branch)
        return {name: jnp.stack([jnp.asarray(row[name]) for row in rows])
                for name in names}

    counters = stacked(stats, ("hc_res_offdiag_max", "hc_res_col_err_max"))
    routers = _routers(stats)
    if routers:
        counters.update(stacked(routers, (
            "router_bias", "expert_tokens", "router_prob_sum",
            "expert_rows_held", "expert_passes")))
        counts = counters["expert_tokens"]
        counters.update(
            expert_load_max_over_mean=jnp.max(counts) / jnp.mean(
                counts.astype(jnp.float32)),
            router_bias_abs_max=jnp.max(jnp.abs(counters["router_bias"])))
    return cross_entropy(x, head, targets), counters


def xing4_loss(params: Dict, batch: Tuple, cfg: Xing4Config):
    return xing4_loss_and_counters(params, batch, cfg)[0]


def make_xing4_train_step(cfg: Xing4Config, optimizer=None,
                          donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step. The
    selection biases are state["held"] [expert layers, n_experts]: the
    loss reads them and moves them by their own rule, no gradient, moment
    or weight decay touches them, and the step keeps what the loss's
    counters say they came to. The step's metrics carry the counters
    beside `loss`."""
    from ._training import make_train_step_for

    def init(key):
        params, biases = split_bias(xing4_init(key, cfg), cfg)
        return params, jnp.stack(biases)

    return make_train_step_for(
        init,
        lambda params, batch, held: xing4_loss_and_counters(
            params, batch, cfg, held),
        axes=split_bias(xing4_param_axes(cfg), cfg)[0],
        optimizer=optimizer, donate=donate, mesh=mesh, rules=rules,
        has_aux=True,
        held_update=lambda biases, counters: counters["router_bias"])
