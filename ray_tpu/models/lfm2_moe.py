"""Gated short convolution / attention / routed-expert decoder (LFM2-MoE
style), TPU-first.

A layer is two branches, x + mixer(rmsnorm(x)) then x + ffn(rmsnorm(x)).
`layer_types` says, layer by layer, what the sequence mixer is: `conv`, a
gated short convolution (models.decoder.short_conv over ops.short_conv:
[B | C | x] = y W_in, a causal depthwise convolution of B * x over
`conv_taps` positions with no bias and no activation, times C, W_out), or
`full_attention`, grouped-query attention with an RMSNorm over each head's
columns of q and of k (one [head_dim] weight each) before rotary
embeddings at `rope_theta`. The channel mixer is named per layer too: the
first `n_dense_layers` run a dense SwiGLU of `d_ff`, every later one an
expert layer (parallel.moe.held_moe_layer, gated): a sigmoid router over
all `n_experts` with a selection bias that no gradient sees,
`experts_per_token` SwiGLU experts of `d_expert` a token, their scores
over their sum + `topk_weight_eps` and times `routed_scale`, no shared
expert. The head is the embedding table. `Lfm2MoeConfig.lfm2_8b_a1b()` is
LiquidAI/LFM2-8B-A1B's config.json (model_type lfm2_moe).

A chip may hold a share of a layer (expert parallelism without its
exchange): `experts_held` = (first, count) of the `n_experts` the router
spans, and `vocab_size` rows of the vocabulary. What the absent experts
would add is left out.

The selection bias is state the optimizer does not own, and it is kept as
models.nemotron_h keeps it, for that module's reasons: `lfm2_moe_init`
returns it inside the tree (`router_bias` in every expert layer: what a
forward, a cache and the benchmark's reference read), at its rule's fixed
point on `balance_tokens` seeded uniform ids, layer by layer, each expert
layer's bias fitted to its own scores before it routes; the train step
keeps it in state["held"], apart from the parameters, and it moves BEFORE
a training step routes: each expert layer runs `bias_rounds` rounds of b
+= r * sign(mean(c) - c) on the step's own scores, from the bias the last
step left (parallel.moe.balance_bias), routes by what that gives, and
hands it on.

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router, taps' sums and softmax. Cache: a `conv`
layer {"conv": the last conv_taps - 1 rows of B * x}, a `full_attention`
layer {"k", "v"}.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION, SHORT_CONV, Decoder, decoder_hidden,
                      decoder_logits, held_gated_experts,
                      keep_kernel_outputs, swiglu_mlp)
from .hybrid import _mlp_init, _normal

CONV, FULL = "conv", "full_attention"
KINDS = {CONV: SHORT_CONV, FULL: ATTENTION}

# The initialiser's balancing tokens go through the stack as sequences of
# this many (fewer where there are fewer), and its biases take this many
# rounds of their rule from zero: parallel.moe.balance_bias's own count to
# its fixed point.
_BALANCE_SEQ = 8192
_FIXED_POINT_ROUNDS = 256


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, d_expert =
    moe_intermediate_size, conv_taps = conv_L_cache, n_dense_layers =
    num_dense_layers, routed_scale = routed_scaling_factor)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    layer_types: Tuple[str, ...] = (CONV, CONV, FULL, CONV)
    n_dense_layers: int = 1         # the leading layers with a dense SwiGLU
    conv_taps: int = 3
    d_ff: int = 1792
    n_experts: int = 32             # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    experts_per_token: int = 4
    d_expert: int = 448
    routed_scale: float = 1.0
    topk_weight_eps: float = 1e-6   # beside the k scores' sum: the model's
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    bias_rounds: int = 48           # of the bias's rule, a training step
    balance_tokens: int = 32768     # 0: the bias starts at zero
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert set(self.layer_types) <= set(KINDS), self.layer_types
        assert self.n_heads % self.n_kv_heads == 0
        assert 0 <= self.n_dense_layers <= self.n_layers
        first, count = self.held
        assert 0 <= first and count > 0 and first + count <= self.n_experts

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def decoder(self, bias_rounds: int = 0) -> Decoder:
        """A sequence mixer a layer by `layer_types`; GQA from `wq` + `wkv`
        with a norm a head; a channel mixer a layer: the dense SwiGLU in
        the first `n_dense_layers`, the held share of the gated experts
        after them, its selection bias as the weights give it or, a
        training step's, moved `bias_rounds` rounds first; under `remat` a
        block keeps what its kernels made and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=self.routed_scale,
            weight_eps=self.topk_weight_eps, bias_rounds=bias_rounds)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            mlp=tuple(swiglu_mlp if i < self.n_dense_layers else experts
                      for i in range(self.n_layers)),
            remat=keep_kernel_outputs if self.remat else None,
            kinds=tuple(KINDS[kind] for kind in self.layer_types),
            rope_base=self.rope_theta, norm_eps=self.norm_eps)

    def init(self, key) -> Dict:
        return lfm2_moe_init(key, self)

    @classmethod
    def tiny(cls) -> "Lfm2MoeConfig":
        """One dense layer, then three expert layers that hold experts 2
        to 5 of 8, the second of them with attention (2 : 1 GQA, heads
        wider than d_model / n_heads): the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=24, layer_types=(CONV, CONV, FULL, CONV),
                   n_dense_layers=1, d_ff=96, n_experts=8,
                   experts_held=(2, 4), experts_per_token=3, d_expert=48,
                   bias_rounds=16, balance_tokens=512, max_seq_len=64)

    @classmethod
    def lfm2_8b_a1b(cls) -> "Lfm2MoeConfig":
        """LiquidAI/LFM2-8B-A1B: 24 layers (18 gated short convolutions, 6
        attention), two dense and 22 expert layers, every expert held;
        8.3 B parameters, about 1.5 B active a token."""
        conv3 = (CONV,) * 3
        return cls(vocab_size=65536, d_model=2048, n_heads=32, n_kv_heads=8,
                   head_dim=64,
                   layer_types=(CONV, CONV, FULL) + (conv3 + (FULL,)) * 4
                   + (CONV, CONV, FULL, CONV, CONV),
                   n_dense_layers=2, conv_taps=3, d_ff=7168, n_experts=32,
                   experts_per_token=4, d_expert=1792, routed_scale=1.0,
                   rope_theta=1000000.0, norm_eps=1e-5,
                   max_seq_len=128000)


def _conv_init(key, cfg: Lfm2MoeConfig, out_scale: float) -> Dict:
    k_in, k_taps, k_out = jax.random.split(key, 3)
    d, bound = cfg.d_model, cfg.conv_taps ** -0.5
    return {
        "conv_in": _normal(k_in, (d, 3 * d), d ** -0.5, cfg.dtype),
        # as torch's Conv1d: uniform in +-1 / sqrt(taps)
        "conv_taps": jax.random.uniform(
            k_taps, (cfg.conv_taps, d), minval=-bound,
            maxval=bound).astype(cfg.dtype),
        "conv_out": _normal(k_out, (d, d), d ** -0.5 * out_scale, cfg.dtype),
    }


def _attention_init(key, cfg: Lfm2MoeConfig, out_scale: float) -> Dict:
    kq, kkv, ko = jax.random.split(key, 3)
    d, q_d = cfg.d_model, cfg.n_heads * cfg.head_dim
    return {
        "wq": _normal(kq, (d, q_d), d ** -0.5, cfg.dtype),
        "wkv": _normal(kkv, (d, 2 * cfg.n_kv_heads * cfg.head_dim),
                       d ** -0.5, cfg.dtype),
        "q_head_norm": jnp.ones((cfg.head_dim,), jnp.float32),
        "k_head_norm": jnp.ones((cfg.head_dim,), jnp.float32),
        "wo": _normal(ko, (q_d, d), q_d ** -0.5 * out_scale, cfg.dtype),
    }


def _experts_init(key, cfg: Lfm2MoeConfig, out_scale: float) -> Dict:
    kr, k1, k2 = jax.random.split(key, 3)
    d, f, held = cfg.d_model, cfg.d_expert, cfg.held[1]
    return {
        # float32: routing decisions are precision-sensitive (models/moe.py)
        "router": jax.random.normal(kr, (d, cfg.n_experts)) * d ** -0.5,
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        # an expert's gate and up matrices side by side, the gate first
        "expert_gate_up": _normal(k1, (held, d, 2 * f), d ** -0.5, cfg.dtype),
        "expert_down": _normal(k2, (held, f, d), f ** -0.5 * out_scale,
                               cfg.dtype),
    }


def _weights(key, cfg: Lfm2MoeConfig) -> Dict:
    """Every parameter, the selection biases zero. Matrices normal 1 /
    sqrt(fan-in), a branch's output matrix divided by sqrt(2 x layers);
    the taps as torch's Conv1d; the table is the head too."""
    keys = jax.random.split(key, cfg.n_layers + 1)
    d, out_scale = cfg.d_model, (2 * cfg.n_layers) ** -0.5
    mixer = {CONV: _conv_init, FULL: _attention_init}

    def layer(i, kind):
        k_mix, *k_ffn = jax.random.split(keys[i + 1], 4)
        ffn = _mlp_init(k_ffn, cfg, out_scale) if i < cfg.n_dense_layers \
            else _experts_init(k_ffn[0], cfg, out_scale)
        return {"ln1": jnp.ones((d,), jnp.float32),
                **mixer[kind](k_mix, cfg, out_scale),
                "ln2": jnp.ones((d,), jnp.float32), **ffn}

    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), d ** -0.5, cfg.dtype),
        "lnf": jnp.ones((d,), jnp.float32),
        "layers": [layer(i, kind) for i, kind in enumerate(cfg.layer_types)],
    }


@functools.partial(jax.jit, static_argnames="cfg")
def _balanced(params: Dict, key, cfg: Lfm2MoeConfig) -> Dict:
    """`params` with every expert layer's selection bias at its rule's
    fixed point on `balance_tokens` seeded uniform ids: the training
    forward (no gradient, no cache) with each expert layer moving its bias
    from zero to the fixed point on its own scores before it routes, so
    every later layer sees the balanced earlier ones."""
    seq = min(cfg.balance_tokens, _BALANCE_SEQ)
    tokens = jax.random.randint(key, (cfg.balance_tokens // seq, seq), 0,
                                cfg.vocab_size)
    dec = cfg.decoder(_FIXED_POINT_ROUNDS)._replace(remat=None)
    stats = decoder_hidden(params, tokens, dec)[2]
    return with_bias(params, [s["router_bias"] for s in stats], cfg)


def lfm2_moe_init(key, cfg: Lfm2MoeConfig) -> Dict:
    """The parameter tree, each expert layer's `router_bias` in it:
    balanced on seeded tokens (the module's docstring), zeros with no
    `balance_tokens`."""
    k_weights, k_tokens = jax.random.split(key)
    params = _weights(k_weights, cfg)
    if cfg.balance_tokens and cfg.n_dense_layers < cfg.n_layers:
        params = _balanced(params, k_tokens, cfg)
    return params


def lfm2_moe_param_axes(cfg: Lfm2MoeConfig) -> Dict:
    mixer = {
        CONV: {"conv_in": ("embed", "mlp"), "conv_taps": (None, "mlp"),
               "conv_out": ("mlp", "embed")},
        FULL: {"wq": ("embed", "mlp"), "wkv": ("embed", "mlp"),
               "q_head_norm": (None,), "k_head_norm": (None,),
               "wo": ("mlp", "embed")},
    }
    dense = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
    experts = {"router": ("embed", None), "router_bias": (None,),
               "expert_gate_up": ("expert", "embed", "mlp"),
               "expert_down": ("expert", "mlp", "embed")}
    return {"embed": ("vocab", "embed"), "lnf": ("embed",),
            "layers": [{"ln1": ("embed",), **mixer[kind], "ln2": ("embed",),
                        **(dense if i < cfg.n_dense_layers else experts)}
                       for i, kind in enumerate(cfg.layer_types)]}


def _expert_layers(cfg: Lfm2MoeConfig):
    return range(cfg.n_dense_layers, cfg.n_layers)


def split_bias(tree: Dict, cfg: Lfm2MoeConfig):
    """A tree with `router_bias` in its expert layers (parameters or their
    axes) -> (the tree without: what the optimizer owns; the biases, one
    an expert layer: what it does not)."""
    biases = [tree["layers"][i]["router_bias"] for i in _expert_layers(cfg)]
    layers = [{k: v for k, v in layer.items() if k != "router_bias"}
              for layer in tree["layers"]]
    return {**tree, "layers": layers}, biases


def with_bias(params: Dict, biases, cfg: Lfm2MoeConfig) -> Dict:
    """`split_bias` undone (`biases` a list or stacked [expert layers,
    n_experts]); no gradient goes to them."""
    layers = list(params["layers"])
    for row, i in enumerate(_expert_layers(cfg)):
        layers[i] = {**layers[i],
                     "router_bias": jax.lax.stop_gradient(biases[row])}
    return {**params, "layers": layers}


def lfm2_moe_forward(params: Dict, tokens, cfg: Lfm2MoeConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def lfm2_moe_loss_and_counters(params: Dict, batch: Tuple,
                               cfg: Lfm2MoeConfig, held=None):
    """(cross entropy, the routers' counters, a row an expert layer), each
    selection bias moved `cfg.bias_rounds` rounds on the batch's own
    scores before its layer routes. `held`: the biases where `params`
    comes without them (the train step's). Counters: `router_bias`
    [expert layers, n_experts] (what the biases came to: the next step's),
    `expert_tokens` and `router_prob_sum` [expert layers, n_experts],
    `expert_rows_held` [expert layers] (the rows the held experts saw),
    `expert_passes` [expert layers] (the passes they took of the layer's
    buffers: 1 while the routing is balanced), `expert_load_max_over_mean`
    (over every router output of every layer), `router_bias_abs_max`."""
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


def lfm2_moe_loss(params: Dict, batch: Tuple, cfg: Lfm2MoeConfig):
    return lfm2_moe_loss_and_counters(params, batch, cfg)[0]


def make_lfm2_moe_train_step(cfg: Lfm2MoeConfig, optimizer=None,
                             donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step. The
    selection biases are state["held"] [expert layers, n_experts]: the
    loss reads them and moves them by their own rule (the module's
    docstring), no gradient, moment or weight decay touches them, and the
    step keeps what the loss's counters say they came to. The step's
    metrics carry the routers' counters beside `loss`."""
    from ._training import make_train_step_for

    def init(key):
        params, biases = split_bias(lfm2_moe_init(key, cfg), cfg)
        return params, jnp.stack(biases)

    return make_train_step_for(
        init,
        lambda params, batch, held: lfm2_moe_loss_and_counters(
            params, batch, cfg, held),
        axes=split_bias(lfm2_moe_param_axes(cfg), cfg)[0],
        optimizer=optimizer, donate=donate, mesh=mesh, rules=rules,
        has_aux=True,
        held_update=lambda biases, counters: counters["router_bias"])
