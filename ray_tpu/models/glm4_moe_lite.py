"""Latent-attention / routed-expert decoder with a multi-token-prediction
module behind it (zai-org's GLM-4.7-Flash, `model_type` glm4_moe_lite:
DeepSeek-V3's attention, router, experts and prediction module on a plain
residual path), TPU-first.

A layer is a pre-norm block of two branches, each added to the one
residual stream. Every sequence mixer is multi-head latent attention
(models.decoder.latent_attention): queries through a `q_lora_rank`-wide
normed latent, ONE `kv_lora_rank`-wide latent and ONE
`qk_rope_head_dim`-wide rotated key a token, a head's no-rope key and its
value made from the normed latent by a second matrix, plain rotary at
`rope_theta` (no scaled context), scores over sqrt(qk_nope_head_dim +
qk_rope_head_dim); a head's value may be wider than its no-rope key (256
against 192 published: q, k and v all 256 wide in the kernels). The channel
mixer is named per layer: the first `n_dense_layers` run a dense SwiGLU of
`d_ff`, every later one an expert layer (parallel.moe.held_moe_layer,
gated): a sigmoid router over all `n_experts` with a selection bias that no
gradient sees, `experts_per_token` SwiGLU experts of `d_expert` a token,
their scores over their sum and times `routed_scale`, beside a shared
SwiGLU expert `n_shared_experts` x `d_expert` wide that every token passes.
The head is untied.

Behind the stack stand `n_predict_layers` (0 or 1) prediction modules
(models.decoder.prediction_module; DeepSeek-V3 section 2.2): with h_i the
last block's output at position i before the final norm and t_{i+1} the
next token, u_i = [rmsnorm(E[t_{i+1}]; enorm) ; rmsnorm(h_i; hnorm)] W_eh,
one more expert-layer block over u (causal over the same positions, its own
router and selection bias), a final norm of its own, and the MAIN head:
logits of t_{i+2}. E and the head are the main model's own arrays, so the
table's gradient is the sum of two lookups' and the head's of two losses'.
The training loss is

    L = CE(main, t_{i+1}) + mtp_loss_weight x CE(module, t_{i+2})

the second over positions 0..S-2 (the last has no target two on and is
masked out of the mean); no balance loss (`noaux_tc`). The module is
training's: `glm4_moe_lite_forward`, prefill and decode run the main stack
alone and never read `params["mtp"]` (serving it as a draft head is
ROADMAP's).

A chip may hold a share of a layer (expert parallelism without its
exchange): `experts_held` = (first, count) of the `n_experts` the router
spans, in the module's block as in the layers', and `vocab_size` rows of
the vocabulary. What the absent experts would add is left out; the shared
expert is whole on every chip.

The selection biases, the module's last, are state the optimizer does not
own, kept as models.xing4 keeps them: the init returns them inside the tree
at their rule's fixed point on `balance_tokens` seeded uniform ids, the
train step keeps them in state["held"] [expert layers + modules,
n_experts], and each moves `bias_rounds` rounds on a step's own scores
BEFORE its layer routes.

Same conventions as models.hybrid: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, router and softmax. Cache: {"latent", "k_rope"} a
layer, 576 values a token at the published sizes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (LATENT_ATTENTION, Decoder, decoder_hidden,
                      decoder_logits, held_gated_experts,
                      keep_kernel_outputs, swiglu_mlp)
from .hybrid import _normal
from .lfm2_moe import _BALANCE_SEQ, _FIXED_POINT_ROUNDS
from .lfm2_moe import split_bias as _split_layers
from .lfm2_moe import with_bias as _with_layers
from .xing4 import _attention_init, _dense_init, _experts_init


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, d_expert =
    moe_intermediate_size, n_experts = n_routed_experts, n_dense_layers =
    first_k_dense_replace, routed_scale = routed_scaling_factor,
    n_predict_layers = num_nextn_predict_layers)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    qk_nope_head_dim: int = 48
    qk_rope_head_dim: int = 16
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
    routed_scale: float = 1.8
    n_predict_layers: int = 1       # prediction modules behind the stack
    mtp_loss_weight: float = 0.3    # the module's loss in the training loss
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    bias_rounds: int = 48           # of the bias's rule, a training step
    balance_tokens: int = 32768     # 0: the bias starts at zero
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.n_predict_layers in (0, 1)
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

    def decoder(self, bias_rounds: int = 0, module: bool = False) -> Decoder:
        """Latent attention in every layer, its widths read off the
        weights, plain rotary; a channel mixer a layer: the dense SwiGLU in
        the first `n_dense_layers`, the held share of the gated experts
        with their shared expert after them, its selection bias as the
        weights give it or, a training step's, moved `bias_rounds` rounds
        first; with `module`, the prediction module's block named after the
        layers (what `decoder_hidden` asks for beside `next_tokens`); under
        `remat` a block keeps its input, the latent and what its kernels
        made and makes the rest again."""
        experts = functools.partial(
            held_gated_experts, experts_per_token=self.experts_per_token,
            first=self.held[0], routed_scale=self.routed_scale,
            weight_eps=1e-20, bias_rounds=bias_rounds)
        blocks = self.n_layers + (self.n_predict_layers if module else 0)
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.qk_head_dim,
            mlp=tuple(swiglu_mlp if i < self.n_dense_layers else experts
                      for i in range(blocks)),
            remat=keep_kernel_outputs if self.remat else None,
            kinds=(LATENT_ATTENTION,) * blocks,
            rope_base=self.rope_theta, norm_eps=self.norm_eps,
            sm_scale=self.qk_head_dim ** -0.5)

    def init(self, key) -> Dict:
        return glm4_moe_lite_init(key, self)

    @classmethod
    def tiny(cls) -> "Glm4MoeLiteConfig":
        """One dense layer, then two expert layers and the module's block
        that hold experts 2 to 5 of 8 beside a shared expert; four heads of
        12 | 4 query and key columns and 16 value columns over a 24-wide
        latent, queries through a 40-wide one: the CPU tests' size."""
        return cls(vocab_size=256, d_model=64, n_heads=4, qk_nope_head_dim=12,
                   qk_rope_head_dim=4, v_head_dim=16, q_lora_rank=40,
                   kv_lora_rank=24, n_layers=3, n_dense_layers=1, d_ff=96,
                   n_experts=8, experts_held=(2, 4), experts_per_token=3,
                   d_expert=24, n_shared_experts=1, routed_scale=1.8,
                   bias_rounds=16, balance_tokens=512, max_seq_len=256)

    @classmethod
    def glm_4_7_flash(cls) -> "Glm4MoeLiteConfig":
        """zai-org/GLM-4.7-Flash: 47 layers, one dense and 46 expert layers
        of 64 routed experts and a shared one, every expert held, one
        prediction module; 30 B parameters, about 3 B active a token."""
        return cls(vocab_size=154880, d_model=2048, n_heads=20,
                   qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                   q_lora_rank=768, kv_lora_rank=512, n_layers=47,
                   n_dense_layers=1, d_ff=10240, n_experts=64,
                   experts_per_token=4, d_expert=1536, n_shared_experts=1,
                   routed_scale=1.8, n_predict_layers=1,
                   rope_theta=1000000.0, norm_eps=1e-5, max_seq_len=202752)


def _layer_init(key, cfg: Glm4MoeLiteConfig, dense: bool = False):
    """A layer's weights, as models.xing4 lays latent attention, a dense
    SwiGLU and a held share of gated experts out."""
    k_mix, k_ffn = jax.random.split(key)
    d = cfg.d_model
    return {"ln1": jnp.ones((d,), jnp.float32),
            **_attention_init(k_mix, cfg),
            "ln2": jnp.ones((d,), jnp.float32),
            **(_dense_init if dense else _experts_init)(k_ffn, cfg)}


def _module_init(key, cfg: Glm4MoeLiteConfig) -> Dict:
    """The prediction module: two input norms, the projection of embedding
    | hidden state side by side (the embedding's rows first, as the
    published `eh_proj` takes them), an expert-layer block, a final norm.
    No table and no head: the main model's."""
    k_eh, k_block = jax.random.split(key)
    d = cfg.d_model
    return {"enorm": jnp.ones((d,), jnp.float32),
            "hnorm": jnp.ones((d,), jnp.float32),
            "w_eh": _normal(k_eh, (2 * d, d), cfg.init_std, cfg.dtype),
            "block": _layer_init(k_block, cfg),
            "norm": jnp.ones((d,), jnp.float32)}


def _weights(key, cfg: Glm4MoeLiteConfig) -> Dict:
    """Every parameter, the selection biases zero. Every matrix normal at
    `init_std`, norms 1; table and head apart."""
    keys = jax.random.split(key, cfg.n_layers + 3)
    d = cfg.d_model
    params = {
        "embed": _normal(keys[0], (cfg.vocab_size, d), cfg.init_std,
                         cfg.dtype),
        "head": _normal(keys[1], (d, cfg.vocab_size), cfg.init_std,
                        cfg.dtype),
        "lnf": jnp.ones((d,), jnp.float32),
        "layers": [_layer_init(keys[i + 3], cfg,
                                      dense=i < cfg.n_dense_layers)
                   for i in range(cfg.n_layers)],
    }
    if cfg.n_predict_layers:
        params["mtp"] = _module_init(keys[2], cfg)
    return params


def split_bias(tree: Dict, cfg: Glm4MoeLiteConfig):
    """A tree with `router_bias` in its expert layers and its module's
    block (parameters or their axes) -> (the tree without: what the
    optimizer owns; the biases, one an expert layer and the module's
    last: what it does not)."""
    tree, biases = _split_layers(tree, cfg)
    if "mtp" in tree:
        block = dict(tree["mtp"]["block"])
        biases = [*biases, block.pop("router_bias")]
        tree = {**tree, "mtp": {**tree["mtp"], "block": block}}
    return tree, biases


def with_bias(params: Dict, biases, cfg: Glm4MoeLiteConfig) -> Dict:
    """`split_bias` undone (`biases` a list or stacked [expert layers +
    modules, n_experts]); no gradient goes to them."""
    params = _with_layers(params, biases, cfg)
    if "mtp" in params:
        block = {**params["mtp"]["block"],
                 "router_bias": jax.lax.stop_gradient(biases[-1])}
        params = {**params, "mtp": {**params["mtp"], "block": block}}
    return params


def _next_targets(targets):
    """What the module is trained on, from the step's `targets` [b, L]
    (t_{i+1} at position i): the tokens two on, t_{i+2}, and the positions
    that have one, all but the last."""
    last = targets.shape[1] - 1
    return (jnp.roll(targets, -1, axis=1),
            jnp.broadcast_to(jnp.arange(last + 1) < last, targets.shape))


def joint_loss(x, x_next, head, targets, weight: float):
    """A step's two cross entropies over the one head -> (L = CE(main) +
    `weight` x CE(module), CE(main), CE(module)): `x` the stack's
    final-norm rows against `targets`, `x_next` the module's against the
    tokens two on, its last position out of the mean (`_next_targets`)."""
    loss_main = cross_entropy(x, head, targets)
    with jax.named_scope("mtp_loss"):       # round the second one's `loss`
        loss_mtp = cross_entropy(x_next, head, *_next_targets(targets))
    return loss_main + weight * loss_mtp, loss_main, loss_mtp


def _hidden(params: Dict, batch: Tuple, cfg: Glm4MoeLiteConfig,
            bias_rounds: int):
    """A training forward: decoder_hidden's four, the rows a pair (the
    stack's, the module's) where the model has a module."""
    tokens, targets = batch
    module = "mtp" in params
    return decoder_hidden(params, tokens, cfg.decoder(bias_rounds, module),
                          next_tokens=targets if module else None)


@functools.partial(jax.jit, static_argnames="cfg")
def _balanced(params: Dict, key, cfg: Glm4MoeLiteConfig) -> Dict:
    """`params` with every selection bias, the module's too, at its rule's
    fixed point on `balance_tokens` seeded uniform ids, as
    models.xing4._balanced: the training forward with each expert layer
    moving its bias from zero on its own scores before it routes."""
    seq = min(cfg.balance_tokens, _BALANCE_SEQ)
    tokens = jax.random.randint(key, (cfg.balance_tokens // seq, seq), 0,
                                cfg.vocab_size)
    stats = _hidden(params, (tokens, jnp.roll(tokens, -1, axis=1)),
                    dataclasses.replace(cfg, remat=False),
                    _FIXED_POINT_ROUNDS)[2]
    return with_bias(params, [s["router_bias"] for s in stats], cfg)


def glm4_moe_lite_init(key, cfg: Glm4MoeLiteConfig) -> Dict:
    """The parameter tree, each expert layer's and the module's
    `router_bias` in it: balanced on seeded tokens (the module's
    docstring), zeros with no `balance_tokens`."""
    k_weights, k_tokens = jax.random.split(key)
    params = _weights(k_weights, cfg)
    if cfg.balance_tokens and (cfg.n_dense_layers < cfg.n_layers
                               or cfg.n_predict_layers):
        params = _balanced(params, k_tokens, cfg)
    return params


def glm4_moe_lite_param_axes(cfg: Glm4MoeLiteConfig) -> Dict:
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

    def layer(ffn):
        return {"ln1": ("embed",), **attention, "ln2": ("embed",), **ffn}

    axes = {"embed": ("vocab", "embed"), "head": ("embed", "vocab"),
            "lnf": ("embed",),
            "layers": [layer(dense if i < cfg.n_dense_layers else experts)
                       for i in range(cfg.n_layers)]}
    if cfg.n_predict_layers:
        axes["mtp"] = {"enorm": ("embed",), "hnorm": ("embed",),
                       "w_eh": (None, "embed"), "block": layer(experts),
                       "norm": ("embed",)}
    return axes


def glm4_moe_lite_forward(params: Dict, tokens, cfg: Glm4MoeLiteConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32: the
    main stack's, the next token's."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def glm4_moe_lite_loss_and_counters(params: Dict, batch: Tuple,
                                    cfg: Glm4MoeLiteConfig, held=None):
    """(the training loss L of the module's docstring, the step's
    counters), each selection bias moved `cfg.bias_rounds` rounds on the
    batch's own scores before its layer routes. `held`: the biases where
    `params` comes without them (the train step's). Counters: `loss_main`
    and `loss_mtp`, the two cross entropies before the weight (`loss_mtp`
    absent where the model has no module); and the routers' as
    models.lfm2_moe's, a row an expert layer and the module's block last:
    `router_bias`, `expert_tokens`, `router_prob_sum` [expert layers +
    modules, n_experts], `expert_rows_held`, `expert_passes` [expert layers
    + modules], `expert_load_max_over_mean`, `router_bias_abs_max`."""
    if held is not None:
        params = with_bias(params, held, cfg)
    x, head, stats, _ = _hidden(params, batch, cfg, cfg.bias_rounds)
    counters = {}
    if stats:
        counters = jax.tree.map(lambda *rows: jnp.stack(rows), *stats)
        counts = counters["expert_tokens"]
        counters.update(
            expert_load_max_over_mean=jnp.max(counts) / jnp.mean(
                counts.astype(jnp.float32)),
            router_bias_abs_max=jnp.max(jnp.abs(counters["router_bias"])))
    targets = batch[1]
    if "mtp" not in params:
        loss = cross_entropy(x, head, targets)
        return loss, {**counters, "loss_main": loss}
    loss, loss_main, loss_mtp = joint_loss(*x, head, targets,
                                           cfg.mtp_loss_weight)
    return loss, {**counters, "loss_main": loss_main, "loss_mtp": loss_mtp}


def glm4_moe_lite_loss(params: Dict, batch: Tuple, cfg: Glm4MoeLiteConfig):
    return glm4_moe_lite_loss_and_counters(params, batch, cfg)[0]


def make_glm4_moe_lite_train_step(cfg: Glm4MoeLiteConfig, optimizer=None,
                                  donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step. The
    selection biases are state["held"] [expert layers + modules,
    n_experts]: the loss reads them and moves them by their own rule, no
    gradient, moment or weight decay touches them, and the step keeps what
    the loss's counters say they came to. The step's metrics carry the
    counters, `loss_main` and `loss_mtp` among them, beside `loss`."""
    from ._training import make_train_step_for

    def init(key):
        params, biases = split_bias(glm4_moe_lite_init(key, cfg), cfg)
        return params, jnp.stack(biases)

    return make_train_step_for(
        init,
        lambda params, batch, held: glm4_moe_lite_loss_and_counters(
            params, batch, cfg, held),
        axes=split_bias(glm4_moe_lite_param_axes(cfg), cfg)[0],
        optimizer=optimizer, donate=donate, mesh=mesh, rules=rules,
        has_aux=True,
        held_update=lambda biases, counters: counters["router_bias"])
