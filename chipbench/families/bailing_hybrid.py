"""bailing_hybrid family (ray_tpu.models.bailing_hybrid): config builder,
operation and byte counts, and a plain float32 reference of
Ling-3.0-flash's layer equations (inclusionAI/Ling-3.0-flash config.json,
model_type bailing_hybrid). The model's own modeling file is not on this
machine: the keys name Kimi Linear's KDA (arXiv:2510.26692), DeepSeek-V2/V3's
latent attention with no query latent and DeepSeek-V3's group-limited
router; every reading is under the configuration file's `assumed`.

The equations (d = 2560, eps 1e-6, no bias on any matrix; T tokens of one
sequence; H = 32 heads in both mixers):

    x_0 = E[token];  layer l:  x <- x + M_l(rmsnorm(x; w1));
                               x <- x + F_l(rmsnorm(x; w2));  h = x_L
    logits = rmsnorm(h; w_f) Head                          (Head untied)
    M = latent attention where (l + 1) mod 6 = 0, KDA elsewhere
    KDA     [q | k | v] = silu(conv4(y W_in)), conv4 a causal depthwise
            convolution of 4 taps with no bias; q, k L2-normalised a head
            of 128 (q x 128^(-1/2) besides); beta = sigmoid(y W_beta) [32];
            g = -5 sigmoid(exp(A_log_h) (y W_f + dt_bias)) in (-5, 0)^{32
            x 128}, float32, ONE NUMBER A KEY CHANNEL;
            S' = Diag(exp(g_t)) S_{t-1}; u_t = beta_t (v_t - S'^T k_t);
            S_t = S' + k_t u_t^T; o_t = S_t^T q_t        (S [128, 128] a head)
            out = (rmsnorm_head(o; [128]) * sigmoid(y W_g)[head]) W_o
    latent  q = y W_q -> a head [q_n 128 | q_r 64];  [c | k_r] = y W_kva;
            c^ = rmsnorm(c; [512]) (eps 1e-6); a head's [k_n | v] = c^
            W_kvb; q_r, k_r rotated over their 64 columns at f_i =
            6e6^(-2i/64), rotate_half form, k_r ONE key under all heads;
            out = (concat_h(causal softmax(q k^T 192^(-1/2)) v) * sigmoid(y
            W_g)[head]) W_o
    F = dense (l < first_k_dense_replace)  W2 (silu(W1 y) * W3 y), 6,144
    F = experts
            s = sigmoid(y W_r) in R^512, float32; b the selection bias (it
            picks and never weighs; a training step's has first moved
            `bias_rounds` rounds of its rule on the batch's own s under the
            same group limit); the 512 experts are 8 groups of 64
            neighbours, a group's mark the sum of its two largest s + b, the
            4 groups with the largest marks are kept, the token's 8 experts
            are the 8 largest s + b inside them; w_j = 2.5 s[e_j] / (sum_j
            s[e_j] + 1e-20);
            out = sum over the HELD e_j of w_j W2[e_j] (silu(W1[e_j] y) *
                  W3[e_j] y), width 768, + the shared SwiGLU expert of 768
    loss = CE(logits, next token)

One chip's share: the file's `num_experts` experts from the first on are
held; what the absent ones would add is left out, here as in the program;
the shared expert is whole. The vocabulary is the file's slice.

The reference runs KDA token by token (`recurrence`), latent attention as a
plain masked softmax over per-head keys and values in query blocks and
EVERY held expert for every token masked by the reference's own routing:
no chunk, no inverse, no latent cache, no sort, no kernel, and no code
shared with ray_tpu. The forms other DeepSeek-V3 references here share (the
rotary, the dense SwiGLU, the norms) are families/xing4.py's and
lfm2_moe.py's. The count functions take the program's config object or the
configuration file's dict and import no jax: per-layer readers call them in
run.py's parent process, which must never initialise a backend."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.machinery
import importlib.util
import math

# A tree from before the family says so as the cell is looked up, in
# run.py's own process, before a cluster or a chip is touched
# (families/granite_hybrid.py has why it is looked for this way).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.bailing_hybrid", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models."
                      "bailing_hybrid: it cannot run a bailing_hybrid "
                      "configuration")

from .lfm2_moe import _blocks, _rms_norm, _sigmoid, _silu  # noqa: E402
from .xing4 import (_all_of, _dense, _rel, _rotary,  # noqa: E402
                    _scale_left_out)

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three, ops/grouped_matmul.py's two, ops/kda.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_gmm_kernel", "_tgmm_kernel",
                  "_kda_fwd_kernel", "_kda_bwd_kernel")
# The trace's Mosaic rows layer_metrics/kda_ms_per_step.py sums and
# kda_flops / kda_bytes count the required work of: the scopes innermost
# round ops/kda.py's two pallas_calls (tests/test_ling3flash_cell_rehearsal
# holds reader and counts to this tuple).
KDA_KERNEL_ROWS = ("kda_fwd", "kda_bwd")

_QUERY_BLOCK = 512
_LOSS_ROWS = 2048
_LATENT_NORM_EPS = 1e-6
_WEIGHT_EPS = 1e-20
_KERNEL_TOKENS = 2048       # kernel_errors' rule: 32 chunks of 64


def build(config: dict, **overrides):
    """The program's BailingHybridConfig at the file's sizes."""
    from ray_tpu.models.bailing_hybrid import BailingHybridConfig

    for key, want in (("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("score_function", "sigmoid"), ("use_bias", False),
                      ("use_qkv_bias", False), ("rope_scaling", None),
                      ("q_lora_rank", None), ("tie_word_embeddings", False),
                      ("hidden_act", "silu"), ("linear_silu", True),
                      ("kda_safe_gate", True), ("no_kda_lora", True),
                      ("value_norm", False), ("group_norm_size", 1),
                      ("num_kv_heads_for_linear_attn", 0),
                      ("moe_router_enable_expert_bias", True),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("num_nextn_predict_layers", 0),
                      ("num_key_value_heads", config["num_attention_heads"])):
        if config[key] != want:
            raise ValueError(f"models/bailing_hybrid.py has {key} = {want!r} "
                             f"only, not {config[key]!r}")
    layers = config["num_hidden_layers"]
    if any(config["expert_swiglu_limit_list"][:layers]) or \
            any(config["share_expert_swiglu_limit_list"][:layers]):
        raise ValueError("models/bailing_hybrid.py clamps no expert: the "
                         "layers built must have a swiglu limit of 0")
    a, sizes = config["assumed"], config["deployment_sizes"]
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              kda_head_dim=config["head_dim"],
              conv_taps=config["short_conv_kernel_size"],
              kda_lower_bound=float(config["kda_lower_bound"]),
              qk_nope_head_dim=config["qk_nope_head_dim"],
              qk_rope_head_dim=config["qk_rope_head_dim"],
              v_head_dim=config["v_head_dim"],
              kv_lora_rank=config["kv_lora_rank"],
              n_layers=layers,
              layer_group_size=config["layer_group_size"],
              n_dense_layers=config["first_k_dense_replace"],
              d_ff=config["intermediate_size"],
              n_experts=sizes["num_experts"],
              experts_held=(sizes["first_expert_held"],
                            config["num_experts"]),
              experts_per_token=config["num_experts_per_tok"],
              n_group=config["n_group"], topk_group=config["topk_group"],
              d_expert=config["moe_intermediate_size"],
              n_shared_experts=config["num_shared_experts"],
              routed_scale=float(config["routed_scaling_factor"]),
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["rms_norm_eps"],
              init_std=a["initializer_range"],
              bias_rounds=a["bias_rounds"],
              balance_tokens=a["balance_tokens"],
              max_seq_len=config["max_position_embeddings"])
    # not a key of config.json: the tiny stand-in's chunk; the program's own
    # 64 where the file says nothing
    if "kda_chunk" in config:
        kw["kda_chunk"] = config["kda_chunk"]
    kw.update(overrides)
    return BailingHybridConfig(**kw)


# The cell's second limit, on what this configuration brought: the largest
# of kernel_errors' relative errors, each the root mean square of got - want
# over that of want, the delta rule's eight taken as their mean (`held`:
# families/olmo_hybrid.py's statistic). Read on the v5e at the published
# sizes on 12 seeds (chipbench/limit_readings.py, my chip runs, PR 63;
# PERF.md section 4): the program 0.010548 to 0.011218, this file's forms
# with every input and value in bfloat16, the nearest precision below,
# 0.015429 to 0.016081; in all 24 readings the worst value is a gradient of
# the whole KDA layer (W_beta's or W_in's for the program, W_beta's or
# A_log's below it). 1.375 times apart at the nearest; the limit is their
# geometric mean, 1.173 times over the program's largest reading and 1.172
# under the lower precision's smallest (ISSUE 63 asked 1.2: the two
# precisions are no further apart on this layer, whose activations are
# bfloat16 in both and whose chain of them is long: projection, convolution,
# norm, rule, norm, gate, projection). By group, program | all-bfloat16: the
# rule's mean 0.00306-0.00322 | 0.00415-0.00450 (1.29 apart; its largest of
# eight 0.00434 | 0.00503), the KDA layer 0.01055-0.01122 | 0.01543-0.01608,
# the latent layer 0.00703-0.00721 | 0.01037-0.01078, the held layer
# 0.00394-0.00404 | 0.00518-0.00547: the lower precision is caught by the
# KDA layer's values. With the tiles' gradient by G taken from q * dq and k *
# dk (each side rounding another factor) the gate's bias read 0.249 here and
# A_log 0.077: the kernel sums the same rounded products on both sides since
# (ops/kda.py `_head_backward`).
KERNEL_LIMIT = 0.01316


def held(errors: dict) -> dict:
    """What KERNEL_LIMIT is a limit on: every value's error but the rule's
    eight (`rule_*`), which count as their mean `rule_mean`."""
    rule = [v for k, v in errors.items() if k.startswith("rule_")]
    out = {k: v for k, v in errors.items() if not k.startswith("rule_")}
    if rule:
        out["rule_mean"] = sum(rule) / len(rule)
    return out


def hold_kernels(cfg):
    """Refuse a program whose delta rule with a decay a channel (g drawn
    over the whole of the gate's range), whole KDA layer, gated latent
    layer or held expert layer under the group limit is further from this
    file's float32 forms than KERNEL_LIMIT: the loss at initialisation,
    which drivers/train.py compares, hardly sees a layer's structure, so
    the cell holds what this configuration brought to a limit of its own
    before it hands the program over."""
    from .. import harness

    errors = held(kernel_errors(cfg))
    _cases.cache_clear()        # its arrays are the chip's, and the step's now
    worst = max(errors, key=lambda k: (math.isnan(errors[k]), errors[k]))
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program is off the float32 reference by {errors[worst]:.3g} "
        f"of the root mean square of {worst} (limit {KERNEL_LIMIT}): {errors}")


# The family's learning rate, for every cell of it (ISSUE 63's rule: the
# largest of 1e-4, 1e-5, 1e-6 at which every layer takes one pass of its
# buffers in every one of 120 steps on six seeds; the readings are in the
# configuration's `assumed.optimizer`). AdamW, weight decay 0.01, no
# schedule. drivers/train.py takes the step this file hands it, so the rate
# lives here; a traffic mix's `optimizer` is prose.
LEARNING_RATE = 1e-4


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    the layers held to KERNEL_LIMIT first where the kernels are the
    chip's (elsewhere tier-1 holds them to the reference)."""
    import jax
    import optax

    from ray_tpu.models import bailing_hybrid as program

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = program.make_bailing_hybrid_train_step(
        cfg, optimizer=optax.adamw(LEARNING_RATE, weight_decay=0.01),
        mesh=mesh, rules=rules)
    return (lambda key: program.bailing_hybrid_init(key, cfg), init_state,
            step, lambda params, batch: program.bailing_hybrid_loss(
                params, batch, cfg))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _decay_a_heads_mean(rule, q, k, v, g, beta, *rest):
    """One decay a head, the mean of its channels': the fault that turns
    KDA into the gated delta rule."""
    import jax.numpy as jnp
    return rule(q, k, v, jnp.broadcast_to(
        jnp.mean(g, axis=-1, keepdims=True), g.shape), beta, *rest)


def _beta_doubled(rule, q, k, v, g, beta, *rest):
    """beta = 2 sigmoid(.) in (0, 2): Gated DeltaNet's negative
    eigenvalues, which this model does not allow."""
    return rule(q, k, v, g, 2.0 * beta, *rest)


def _rule_by_plain_form(fault: str):
    """The rule as this file's chunked plain form in float32, one line of
    it wrong."""
    def faulty(rule, q, k, v, g, beta, chunk=64, initial_state=None,
               lower_bound=-5.0):
        o, S = chunked(q, k, v, g, beta, initial_state, chunk, fault)
        return o.astype(v.dtype), S
    return faulty


def _l2_norm_dropped(unit, t, heads: int, scale: float, eps: float):
    """q and k as the convolution leaves them, cut into heads and scaled
    but not normalised (stands for models.decoder._unit_heads)."""
    b, L, _ = t.shape
    return (t.reshape(b, L, heads, -1) * scale).astype(t.dtype)


def _kda_by_plain_form(fault: str):
    """The KDA mixer as this file's plain form in the program's own
    precision, one line of it wrong (stands for models.decoder.kda)."""
    def faulty(mixer, x, layer, dec, cache=None, start_pos=None):
        import jax.numpy as jnp
        y = _rms_norm(x.astype(jnp.float32), layer["ln1"],
                      dec.norm_eps).astype(x.dtype)
        out = _kda(y, layer, _KdaSizes(
            layer["A_log"].shape[0], layer["kda_norm"].shape[0],
            dec.kda_lower_bound, dec.norm_eps), fault)
        return out.astype(x.dtype), None, {"kda_log_decay_min": jnp.zeros(())}
    return faulty


def _latent_gate_left_out(mixer, x, layer, dec, cache=None, start_pos=None):
    """concat_h(P v) W_o with no gate."""
    return mixer(x, {k: v for k, v in layer.items() if k != "head_gate"},
                 dec, cache, start_pos)


def _group_limit_ignored(layer, *operands, **sizes):
    """The top 8 of all 512, no group dropped."""
    sizes.pop("n_group", None)
    sizes.pop("topk_group", None)
    return layer(*operands, **sizes)


def _mark_is_the_best_one(within, biased, n_group=1, topk_group=1):
    """A group's mark its largest score, not the sum of its two largest
    (DeepSeek-V2's device limit, not V3's)."""
    import jax
    import jax.numpy as jnp

    if n_group == 1:
        return biased, None
    n, e = biased.shape
    grouped = biased.reshape(n, n_group, e // n_group)
    kept = jax.lax.top_k(jnp.max(grouped, -1), topk_group)[1]
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], grouped,
                     -jnp.inf).reshape(n, e), keep


# What limit_readings.py plants in the program, one at a time, each a fault
# of structure in what this configuration brought: (the module and the name
# on it that stands for the faulty one meanwhile, the faulty one given the
# real one first, the group of kernel_errors it moves).
_DECODER, _MOE = "ray_tpu.models.decoder", "ray_tpu.parallel.moe"
STRUCTURAL_FAULTS = {
    "decay_a_heads_mean": (_DECODER, "kda_rule", _decay_a_heads_mean, "rule"),
    "correction_dropped": (_DECODER, "kda_rule",
                           _rule_by_plain_form("correction_dropped"), "rule"),
    "chunk_carry_dropped": (_DECODER, "kda_rule",
                            _rule_by_plain_form("chunk_carry_dropped"),
                            "rule"),
    "decay_left_off_q": (_DECODER, "kda_rule",
                         _rule_by_plain_form("decay_left_off_q"), "rule"),
    "beta_doubled": (_DECODER, "kda_rule", _beta_doubled, "rule"),
    "l2_norm_dropped": (_DECODER, "_unit_heads", _l2_norm_dropped, "rule"),
    "softplus_gate": (_DECODER, "kda", _kda_by_plain_form("softplus_gate"),
                      "kda"),
    "bound_left_out": (_DECODER, "kda", _kda_by_plain_form("bound_left_out"),
                       "kda"),
    "silu_dropped": (_DECODER, "kda", _kda_by_plain_form("silu_dropped"),
                     "kda"),
    "gate_a_channels": (_DECODER, "kda",
                        _kda_by_plain_form("gate_a_channels"), "kda"),
    "gate_left_out": (_DECODER, "kda", _kda_by_plain_form("gate_left_out"),
                      "kda"),
    "latent_gate_left_out": (_DECODER, "latent_attention",
                             _latent_gate_left_out, "mla"),
    "group_limit_ignored": (_DECODER, "held_moe_layer", _group_limit_ignored,
                            "moe"),
    "mark_is_the_best_one": (_MOE, "within_groups", _mark_is_the_best_one,
                             "moe"),
    "routed_scale_left_out": (_DECODER, "held_moe_layer", _scale_left_out,
                              "moe"),
}
PRECISION_FAULTS = {}
_PLANTED_GROUP = []


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault`: the program calls the rule, the
    normalisation, the mixers, the expert layer and the group choice
    through their modules' own names, one of which stands for the faulty
    one meanwhile. Trace the program inside; a function jitted before keeps
    what it traced."""
    where, name, faulty, group = STRUCTURAL_FAULTS[fault]
    module = importlib.import_module(where)
    real = getattr(module, name)
    setattr(module, name, functools.partial(faulty, real))
    _PLANTED_GROUP[:] = [group]
    try:
        yield
    finally:
        setattr(module, name, real)
        _PLANTED_GROUP.clear()


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's BailingHybridConfig or the configuration's
    dict. `held` experts of `e` the router spans; `kda_layers` and
    `latent_layers` by the layers' places."""
    if isinstance(cfg, dict):
        layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        s = dict(
            d=cfg["hidden_size"], v=cfg["vocab_size"],
            h=cfg["num_attention_heads"], K=cfg["head_dim"],
            taps=cfg["short_conv_kernel_size"], n=cfg["qk_nope_head_dim"],
            r=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
            c=cfg["kv_lora_rank"], ff=cfg["intermediate_size"],
            e=cfg["deployment_sizes"]["num_experts"],
            held=cfg["num_experts"], k=cfg["num_experts_per_tok"],
            f=cfg["moe_intermediate_size"],
            fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
            layers=layers, dense_layers=dense, period=cfg["layer_group_size"])
    else:
        s = dict(
            d=cfg.d_model, v=cfg.vocab_size, h=cfg.n_heads,
            K=cfg.kda_head_dim, taps=cfg.conv_taps, n=cfg.qk_nope_head_dim,
            r=cfg.qk_rope_head_dim, vd=cfg.v_head_dim, c=cfg.kv_lora_rank,
            ff=cfg.d_ff, e=cfg.n_experts, held=cfg.held[1],
            k=cfg.experts_per_token, f=cfg.d_expert, fs=cfg.d_shared,
            layers=cfg.n_layers, dense_layers=cfg.n_dense_layers,
            period=cfg.layer_group_size)
    s["latent_layers"] = s["layers"] // s["period"]
    s["kda_layers"] = s["layers"] - s["latent_layers"]
    s["expert_layers"] = s["layers"] - s["dense_layers"]
    return s


def _held_rows(s: dict, tokens: int) -> float:
    """Rows a layer's held experts see under a balanced router: every
    token's k assignments fall evenly on the e experts."""
    return tokens * s["k"] * s["held"] / s["e"]


def held_rows_balanced(cfg, tokens: int) -> float:
    """The rows a layer's held experts see a step of `tokens` under a
    balanced router: what the counts below take the routed work to be, and
    what the step's `expert_rows_held` is read against."""
    return _held_rows(_dims(cfg), tokens)


def _recurrence_flops_per_token(s: dict) -> float:
    """The three K x V products a token and head that the recurrence
    itself has (S'^T k, k u^T, S^T q) and the decay of the state, K x V
    multiplications: 7 K V, whatever the chunking."""
    return 7.0 * s["K"] * s["K"] * s["h"]


def _chunk_flops_per_token(s: dict, chunk: int = 64) -> float:
    """What section 1's chunked form needs a token and head: the two P
    tiles over their causal pairs (a product and a decay a channel and
    pair: 2 x 2 K x (chunk + 1) / 2), W and U (the triangle: chunk K each
    way), V' (2 K K), O (2 K K from the state, (chunk + 1) K from the
    chunk) and S_next (2 K K). No inverse, no sub-block, nothing made
    again."""
    K = s["K"]
    tri = (chunk + 1) / 2
    return s["h"] * (2 * 2 * K * tri + 2 * 2 * K * tri + 2 * K * K
                     + 2 * K * K + 2 * K * tri + 2 * K * K)


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul and convolution operations one token needs in the forward
    pass at context `seq`. A KDA layer: W_in (q | k | v), W_f, W_beta, W_g
    and W_o, the convolution's taps and the chunked rule's products
    (`_chunk_flops_per_token`). A latent layer: W_q, W_kva, W_kvb, W_g,
    W_o and causal attention (QK^T over n + r columns and PV over vd, half
    the square). A dense layer's three matrices. An expert layer: the
    router over all e outputs, the shared expert's three matrices and the
    BALANCED share of the routed work (k held / e assignments a token,
    three matmuls each); nothing made again. The untied head once."""
    s = _dims(cfg)
    d, h, K, qk = s["d"], s["h"], s["K"], s["n"] + s["r"]
    kda = (2 * d * 3 * h * K + 2 * d * h * K + 2 * 2 * d * h
           + 2 * h * K * d + 2 * s["taps"] * 3 * h * K
           + _chunk_flops_per_token(s))
    latent = (2 * d * h * qk + 2 * d * (s["c"] + s["r"])
              + 2 * s["c"] * h * (s["n"] + s["vd"]) + 2 * d * h
              + 2 * h * s["vd"] * d + 2 * seq * h * (qk + s["vd"]) / 2)
    dense = 3 * 2 * d * s["ff"]
    experts = (2 * d * s["e"] + 3 * 2 * d * s["fs"]
               + _held_rows(s, 1) * 3 * 2 * d * s["f"])
    return (s["kda_layers"] * kda + s["latent_layers"] * latent
            + s["dense_layers"] * dense + s["expert_layers"] * experts
            + 2 * d * s["v"])


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the kernels' tiles made again in their backward) is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step, the
    latent layers' alone: forward QK^T (n + r wide) and PV (vd wide);
    backward dV and dP (vd wide), dQ and dK (n + r wide); each
    2*B*H*S*S*width, halved for the causal mask."""
    s = _dims(cfg)
    qk, vd = s["n"] + s["r"], s["vd"]
    return (s["latent_layers"] * 2 * batch * seq * seq * s["h"]
            * ((qk + vd) + 2 * (qk + vd)) / 2)


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv, k and v
    at the 32 heads the kernels are handed. bf16."""
    s = _dims(cfg)
    wide = batch * seq * s["h"] * (s["n"] + s["r"]) * 2
    narrow = batch * seq * s["h"] * s["vd"] * 2
    return s["latent_layers"] * (
        (2 * wide + 2 * narrow) + (4 * wide + 4 * narrow))


def expert_matmul_flops(cfg, tokens: int) -> float:
    """Required operations of the grouped matmuls in one train step for a
    BALANCED router: the held experts' rows (tokens x k x held / e a
    layer) go through three matmuls forward (gate, up, down) and six
    backward, 2 * rows * d * f each. What remat makes again is not
    counted; the shared expert is no grouped matmul."""
    s = _dims(cfg)
    return (s["expert_layers"] * (3 + 6) * 2.0 * _held_rows(s, tokens)
            * s["d"] * s["f"])


def expert_matmul_bytes(cfg, tokens: int) -> float:
    """Least HBM traffic of those nine matmuls a layer: each touches its
    rows [rows, d], the held experts' tensor [held, d, f] and its other
    rows [rows, f] once. bf16."""
    s = _dims(cfg)
    one = (_held_rows(s, tokens) * (s["d"] + s["f"])
           + s["held"] * s["d"] * s["f"])
    return s["expert_layers"] * (3 + 6) * 2.0 * one


def kda_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the KDA kernels (`KDA_KERNEL_ROWS`) in one
    train step, the KDA layers only: what section 1's chunked form needs a
    token and head forward (`_chunk_flops_per_token`: the two P tiles over
    their causal pairs, W, U, V', O, S_next) and twice that backward. No
    inverse, no sub-block bookkeeping and nothing made again is counted,
    so the share cannot pass 100% and reads the same work if a later PR
    rewrites the kernels."""
    s = _dims(cfg)
    return s["kda_layers"] * 3.0 * batch * seq * _chunk_flops_per_token(s)


def kda_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: q, k, v, g, beta and dO read
    and o, dq, dk, dv, dg, dbeta written once in the dtypes the program
    passes them (q, k, v, o and their gradients bf16; g [K wide a head],
    beta and theirs float32), and the state entering each chunk of 64,
    float32 [K, K] a head, written by the forward and read by the backward
    once each."""
    s = _dims(cfg)
    H, K = s["h"], s["K"]
    token = 8 * H * K * 2 + 2 * H * K * 4 + 2 * H * 4
    states = 2 * (seq // 64) * H * K * K * 4
    return s["kda_layers"] * batch * (seq * token + states)


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _KdaSizes:
    H: int
    K: int
    bound: float
    eps: float


def _l2(t, eps):
    import jax.numpy as jnp
    return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + eps)


def recurrence(q, k, v, g, beta, initial_state=None):
    """The delta rule with a decay a key channel as it is defined, one
    token after another: S' = Diag(exp(g_t)) S_{t-1}; u_t = beta_t (v_t -
    S'^T k_t); S_t = S' + k_t u_t^T; o_t = S_t^T q_t. q, k, g [b, s, H, K]
    (q, k normalised by the caller), v [b, s, H, V], beta [b, s, H].
    Returns (o [b, s, H, V], the final state [b, H, K, V]), in v's dtype,
    which the state has at every step."""
    import jax
    import jax.numpy as jnp

    dtype = v.dtype
    b, s, H, K = q.shape

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = (jnp.exp(g_t)[..., None] * S).astype(dtype)
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = (S + k_t[..., :, None] * u.astype(dtype)[..., None, :]
             ).astype(dtype)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t).astype(dtype)

    # Memory only, not mathematics (families/olmo_hybrid.py recurrence).
    block = _blocks(s, 64)

    @jax.checkpoint
    def tokens(S, ts):
        return jax.lax.scan(step, S, ts)

    if initial_state is None:
        initial_state = jnp.zeros((b, H, K, v.shape[-1]), dtype)
    S, o = jax.lax.scan(tokens, initial_state.astype(dtype), tuple(
        t.astype(dtype).swapaxes(0, 1).reshape(s // block, block,
                                               *t.shape[:1], *t.shape[2:])
        for t in (q, k, v, g, beta)))
    return o.reshape(s, *o.shape[2:]).swapaxes(0, 1), S


def chunked(q, k, v, g, beta, initial_state=None, chunk: int = 64,
            fault=None):
    """Section 1's chunked form in plain float32 jax.numpy, a chunk a scan
    step, every pair's decay exp(G_i - G_j) made outright as a [C, C, K]
    array (no reference row, no sub-block) and T by a triangular solve:
    the planted faults' form, and a second reading of the recurrence."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular

    f32 = jnp.float32
    b, L, H, K = q.shape
    V, nc = v.shape[-1], L // chunk

    def by_chunk(t):            # [b, L, H, ...] -> [nc, b, H, C, ...]
        t = t.astype(f32).reshape(b, nc, chunk, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]

    def one(S, c):
        q_c, k_c, v_c, g_c, b_c = c                 # [b, H, C, .]
        G = jnp.cumsum(g_c, axis=2)
        seg = G[:, :, :, None, :] - G[:, :, None, :, :]     # [b,H,C,C,K]
        D = jnp.exp(jnp.where((rows >= cols)[..., None], seg, -jnp.inf))
        Pkk = jnp.einsum("bhik,bhjk,bhijk->bhij", k_c, k_c, D)
        Pqk = jnp.einsum("bhik,bhjk,bhijk->bhij", q_c, k_c, D)
        bc = b_c[..., None]
        A = jnp.where(rows > cols, bc * Pkk, 0.0)
        eg = jnp.exp(G)
        if fault == "correction_dropped":
            A = jnp.zeros_like(A)
        WU = solve_triangular(
            A + jnp.eye(chunk, dtype=f32),
            jnp.concatenate([bc * eg * k_c, bc * v_c], -1), lower=True,
            unit_diagonal=True)
        W, U = WU[..., :K], WU[..., K:]
        if fault == "correction_dropped":
            W = jnp.zeros_like(W)
        S_in = jnp.zeros_like(S) if fault == "chunk_carry_dropped" else S
        Vp = U - jnp.einsum("bhik,bhkv->bhiv", W, S_in)
        q_dec = q_c if fault == "decay_left_off_q" else q_c * eg
        o = (jnp.einsum("bhik,bhkv->bhiv", q_dec, S_in)
             + jnp.einsum("bhij,bhjv->bhiv",
                          jnp.where(rows >= cols, Pqk, 0.0), Vp))
        end = G[:, :, -1:, :]
        S = (jnp.exp(end).swapaxes(-1, -2) * S_in
             + jnp.einsum("bhik,bhiv->bhkv", k_c * jnp.exp(end - G), Vp))
        return S, o

    S0 = jnp.zeros((b, H, K, V), f32) if initial_state is None \
        else initial_state.astype(f32)
    S, o = jax.lax.scan(one, S0, tuple(
        by_chunk(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, L, H, V)
    return o, S


def _conv_silu(x, w, fault=None):
    """silu of the causal depthwise convolution of x [b, s, C] under w [C,
    taps], the last tap on the current position, zeros before the first."""
    import jax.numpy as jnp

    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + s] * w[:, i].astype(x.dtype)
            for i in range(taps))
    return y if fault == "silu_dropped" else _silu(y)


def _kda(y, lay, z: _KdaSizes, fault=None, state=None):
    """y [b, s, d], already normed -> [b, s, d]."""
    import jax.numpy as jnp

    b, s, _ = y.shape
    H, K = z.H, z.K
    q, k, v = jnp.split(_conv_silu(y @ lay["kda_in"], lay["conv_w"], fault),
                        3, axis=-1)
    heads = (b, s, H, K)
    q = (_l2(q.reshape(heads), z.eps) * K ** -0.5).astype(y.dtype)
    k = _l2(k.reshape(heads), z.eps).astype(y.dtype)
    f = (y @ lay["kda_f"] + lay["dt_bias"].astype(y.dtype)).reshape(heads)
    rate = jnp.exp(lay["A_log"].astype(y.dtype))[:, None]
    if fault == "softplus_gate":        # Kimi Linear's own gate, unbounded
        g = -rate * jnp.logaddexp(f, 0.0)
    elif fault == "bound_left_out":
        g = -_sigmoid(rate * f)
    else:
        g = z.bound * _sigmoid(rate * f)
    beta = _sigmoid(y @ lay["kda_beta"])
    o, _ = recurrence(q, k, v.reshape(heads), g.astype(y.dtype), beta, state)
    o = _rms_norm(o, lay["kda_norm"].astype(o.dtype), z.eps)
    gate = _sigmoid(y @ lay["head_gate"])                   # [b, s, H]
    if fault == "gate_a_channels":
        # a gate a channel from the weights there are: channel c of head h
        # under the gate of head (h + c) mod H
        idx = (jnp.arange(H)[:, None] + jnp.arange(K)[None, :]) % H
        o = o * gate[:, :, idx]
    elif fault != "gate_left_out":
        o = o * gate[..., None]
    return o.reshape(b, s, H * K).astype(y.dtype) @ lay["kda_out"]


def _latent(y, lay, cfg, fault=None):
    """y [b, s, d], already normed -> [b, s, d]: queries straight from y,
    per-head keys and values from the normed latent, the one rotated key
    under every head, one gate a head; query blocks against all keys."""
    import jax
    import jax.numpy as jnp

    b, s, _ = y.shape
    h, n, r, vd = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                   cfg.v_head_dim)
    inv_freq = [cfg.rope_theta ** (-2.0 * i / r) for i in range(r // 2)]
    scale = (n + r) ** -0.5
    q = (y @ lay["wq"]).reshape(b, s, h, n + r)
    c, k_r = jnp.split(y @ lay["w_kva"], [cfg.kv_lora_rank], axis=-1)
    c = _rms_norm(c, lay["latent_norm"].astype(c.dtype), _LATENT_NORM_EPS)
    k_n, v = jnp.split((c @ lay["w_kvb"]).reshape(b, s, h, n + vd), [n],
                       axis=-1)
    k_r = jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, r))
    q = jnp.concatenate([q[..., :n], _rotary(q[..., n:], inv_freq)], -1)
    k = jnp.concatenate([k_n, _rotary(k_r, inv_freq)], -1)
    block = _blocks(s, _QUERY_BLOCK)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, first = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) * scale
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    out = jax.lax.map(one_block, (
        q.reshape(b, s // block, block, h, n + r).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    out = out.swapaxes(0, 1).reshape(b, s, h, vd)
    if fault != "latent_gate_left_out":
        out = out * _sigmoid(y @ lay["head_gate"])[..., None]
    return out.reshape(b, s, h * vd).astype(y.dtype) @ lay["wo"]


def _narrowed(biased, n_group: int, topk_group: int):
    """`biased` [T, E] with every expert outside the token's `topk_group`
    groups at -inf: a group's mark the sum of its two largest, by a sort."""
    import jax.numpy as jnp

    if n_group == 1:
        return biased
    t, e = biased.shape
    grouped = biased.reshape(t, n_group, e // n_group)
    marks = jnp.sum(-jnp.sort(-grouped, axis=-1)[..., :2], axis=-1)
    bar = -jnp.sort(-marks, axis=-1)[:, topk_group - 1:topk_group]
    return jnp.where((marks >= bar)[..., None], grouped,
                     -jnp.inf).reshape(t, e)


def _bias_moved(scores, bias, k: int, rounds: int, n_group: int,
                topk_group: int):
    """families/lfm2_moe.py `_bias_moved` under the group limit: the groups
    are chosen anew at every ranking, under the bias as it then stands, and
    an expert outside a token's groups clears no bar there."""
    import jax.numpy as jnp

    scores, bias = scores.astype(jnp.float32), bias.astype(jnp.float32)
    tokens, e = scores.shape
    spread = jnp.maximum(jnp.max(scores) - jnp.min(scores), 1e-4)
    steps = spread * (1e-4 / spread) ** jnp.linspace(0.0, 1.0, rounds)
    for first in range(0, rounds, 8):
        biased = _narrowed(scores + bias, n_group, topk_group)
        ranked = -jnp.sort(-biased, axis=-1)
        last_in, first_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
        over_bar = biased - jnp.where(biased >= last_in, first_out, last_in)
        moved = jnp.zeros_like(bias)
        for r in steps[first:first + 8]:
            count = jnp.sum(over_bar + moved > 0, axis=0).astype(jnp.float32)
            moved = moved + r * jnp.sign(tokens * k / e - count)
        bias = bias + moved
    return bias - jnp.mean(bias)


def _plain_experts(y, router, bias, gate_up, down, shared_gate_up,
                   shared_down, *, k: int, first: int, scale: float,
                   n_group: int, topk_group: int, chosen=None,
                   rounds: int = 0):
    """y [T, d] -> (the held experts' part plus the shared expert's [T, d],
    the chosen experts [T, k]). Every held expert runs on every token and
    is weighted by the routing's mask; `chosen` given, the routing is that
    one and not the reference's own; with `rounds` the bias moves that many
    rounds on these scores first."""
    import jax
    import jax.numpy as jnp

    scores = _sigmoid(y.astype(router.dtype) @ router)
    if rounds:
        bias = jax.lax.stop_gradient(_bias_moved(
            scores, bias, k, rounds, n_group, topk_group)).astype(
                scores.dtype)
    if chosen is None:
        chosen = jax.lax.top_k(
            _narrowed(scores + bias, n_group, topk_group), k)[1]
    w = jnp.take_along_axis(scores, chosen, -1)
    w = scale * w / (jnp.sum(w, -1, keepdims=True) + _WEIGHT_EPS)
    held_n = gate_up.shape[0]
    weight = jnp.sum(
        jax.nn.one_hot(chosen - first, held_n, dtype=w.dtype) * w[..., None],
        1)

    def one_expert(acc, xs):
        gu, dn, w_e = xs
        w1, w3 = jnp.split(gu, 2, axis=-1)
        out = (_silu(y @ w1) * (y @ w3)) @ dn
        return acc + w_e[:, None].astype(acc.dtype) * out, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          (gate_up, down, weight.T))
    s1, s3 = jnp.split(shared_gate_up, 2, axis=-1)
    return out + (_silu(y @ s1) * (y @ s3)) @ shared_down, chosen


def _experts(y, lay, cfg, rounds=0):
    b, s, d = y.shape
    out, _ = _plain_experts(
        y.reshape(b * s, d), lay["router"], lay["router_bias"],
        lay["expert_gate_up"], lay["expert_down"], lay["shared_gate_up"],
        lay["shared_down"], k=cfg.experts_per_token, first=cfg.held[0],
        scale=cfg.routed_scale, n_group=cfg.n_group,
        topk_group=cfg.topk_group, rounds=rounds)
    return out.reshape(b, s, d)


def _kda_sizes(cfg) -> _KdaSizes:
    return _KdaSizes(cfg.n_heads, cfg.kda_head_dim, cfg.kda_lower_bound,
                     cfg.norm_eps)


def _hidden(params, tokens, cfg, dtype=None, rounds=0):
    """(final-norm rows [b, s, d], the head [d, V]), every parameter and so
    every value in `dtype` (float32 unless given); `rounds` of each
    selection bias's rule before its layer routes."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    eps = cfg.norm_eps
    x = p["embed"][tokens]
    for i, lay in enumerate(p["layers"]):
        y = _rms_norm(x, lay["ln1"], eps)
        if (i + 1) % cfg.layer_group_size == 0:
            x = x + _latent(y, lay, cfg)
        else:
            x = x + _kda(y, lay, _kda_sizes(cfg))
        y = _rms_norm(x, lay["ln2"], eps)
        x = x + (_dense(y, lay) if i < cfg.n_dense_layers
                 else _experts(y, lay, cfg, rounds))
    return _rms_norm(x, p["lnf"], eps), p["head"]


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    x, head = _hidden(params, tokens, cfg)
    return x @ head


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """Mean next-token cross entropy of a training step's forward (each
    selection bias moved `cfg.bias_rounds` rounds on the batch first), in
    float32, the logits a block of rows at a time. `dtype` is for setting
    the comparison's limit only: the same reference with every parameter
    and value in a lower precision (bfloat16) has to come out as not
    correct (PERF.md)."""
    import jax
    import jax.numpy as jnp

    x, head = _hidden(params, tokens, cfg, dtype, cfg.bias_rounds)
    rows = x.reshape(-1, x.shape[-1])
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), -1)
        return jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1))

    total = jax.lax.map(one_block, (rows.reshape(-1, block, rows.shape[-1]),
                                    targets.reshape(-1, block)))
    return -jnp.sum(total) / targets.size


# ---------------------------------------------------------------------------
# the layers this configuration brought, against the forms above
# ---------------------------------------------------------------------------
_KDA_NAMES = ("ln1", "kda_in", "conv_w", "kda_f", "A_log", "dt_bias",
              "kda_beta", "head_gate", "kda_norm", "kda_out")
_MLA_NAMES = ("ln1", "wq", "w_kva", "latent_norm", "w_kvb", "head_gate", "wo")


def _rule_all_of(fn):
    """o, the final state and the six gradients of a weighted sum of both,
    one program: (the weights of o and of the state, the six inputs). The
    weights are handed in, not closed over: 35 MB of constants in an
    executable are compiled again every run (the persistent cache holds
    192 MiB)."""
    import jax
    import jax.numpy as jnp

    def scalar(*given, weights):
        o, state = fn(*given)
        return (jnp.sum(o.astype(jnp.float32) * weights[0])
                + jnp.sum(state.astype(jnp.float32) * weights[1])), (o, state)

    def run(weights, *given):
        (_, (o, state)), grads = jax.value_and_grad(
            functools.partial(scalar, weights=weights),
            argnums=tuple(range(6)), has_aux=True)(*given)
        return (o, state, *grads)
    return jax.jit(run)


@functools.lru_cache(maxsize=2)
def _cases(cfg, seed: int) -> dict:
    """kernel_errors' seeded inputs and what this file's float32 forms give
    on them, once a (configuration, seed): the program, the all-bfloat16
    forms and every planted fault are read against the same values."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d, E, k = cfg.d_model, cfg.n_experts, cfg.experts_per_token
    H, K = cfg.n_heads, cfg.kda_head_dim
    first, held_n = cfg.held
    f, fs = cfg.d_expert, cfg.d_shared
    chunk = cfg.kda_chunk
    normal = jax.random.normal
    key = jax.random.PRNGKey(seed)
    z = _kda_sizes(cfg)

    def matrix(kk, shape, dtype=cfg.dtype):
        return (normal(kk, shape) * shape[-2] ** -0.5).astype(dtype)

    def scale(kk, width):
        return 1.0 + 0.1 * normal(kk, (width,))

    # -- the rule: g drawn over the whole of (bound, 0) ---------------------
    L = _KERNEL_TOKENS // chunk * chunk if chunk >= 64 else 4 * chunk
    kr = jax.random.split(jax.random.fold_in(key, 1), 9)
    rule_in = (
        jax.nn.silu(normal(kr[0], (1, L, H * K))).astype(cfg.dtype),
        jax.nn.silu(normal(kr[1], (1, L, H * K))).astype(cfg.dtype),
        jax.nn.silu(normal(kr[2], (1, L, H, K))).astype(cfg.dtype),
        z.bound * jax.random.uniform(kr[3], (1, L, H, K)),            # g
        jax.nn.sigmoid(normal(kr[4], (1, L, H))),                     # beta
        normal(kr[5], (1, H, K, K)))                        # initial state
    rule_w = (normal(kr[6], (1, L, H, K)), normal(kr[7], (1, H, K, K)))

    def rule_plain(dtype):
        def fn(*given):
            q, kk, v, g, beta, init = (t.astype(dtype) for t in given)
            heads = (*q.shape[:2], H, K)
            q = (_l2(q.reshape(heads), z.eps) * K ** -0.5).astype(dtype)
            kk = _l2(kk.reshape(heads), z.eps).astype(dtype)
            return recurrence(q, kk, v, g, beta, init)
        return fn

    # -- a whole KDA layer --------------------------------------------------
    rows_a = 1024 // chunk * chunk if chunk >= 64 else 4 * chunk
    kk_ = jax.random.split(jax.random.fold_in(key, 2), 12)
    kda_layer = {
        "ln1": scale(kk_[1], d), "kda_in": matrix(kk_[2], (d, 3 * H * K)),
        "conv_w": normal(kk_[3], (3 * H * K, cfg.conv_taps)) * 0.5,
        "kda_f": matrix(kk_[4], (d, H * K)),
        "A_log": 0.3 * normal(kk_[5], (H,)),
        "dt_bias": 0.5 * normal(kk_[6], (H * K,)),
        "kda_beta": matrix(kk_[7], (d, H)),
        "head_gate": matrix(kk_[8], (d, H)),
        "kda_norm": scale(kk_[9], K), "kda_out": matrix(kk_[10], (H * K, d))}
    kda_in = (normal(kk_[0], (1, rows_a, d)).astype(cfg.dtype),
              *(kda_layer[n] for n in _KDA_NAMES))
    kda_w = (normal(kk_[11], (1, rows_a, d)),)

    def kda_plain(dtype):
        def fn(x, *weights):
            lay = {n: w.astype(dtype) for n, w in zip(_KDA_NAMES, weights)}
            y = _rms_norm(x.astype(dtype), lay["ln1"], cfg.norm_eps)
            return (_kda(y, lay, z),)
        return fn

    # -- a latent-attention layer with its gate -----------------------------
    ka = jax.random.split(jax.random.fold_in(key, 3), 9)
    h, c, r, n, vd = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                      cfg.qk_nope_head_dim, cfg.v_head_dim)
    mla_layer = {
        "ln1": scale(ka[1], d), "wq": matrix(ka[2], (d, h * (n + r))),
        "w_kva": matrix(ka[3], (d, c + r)), "latent_norm": scale(ka[4], c),
        "w_kvb": matrix(ka[5], (c, h * (n + vd))),
        "head_gate": matrix(ka[6], (d, h)), "wo": matrix(ka[7], (h * vd, d))}
    mla_in = (normal(ka[0], (1, 1024, d)).astype(cfg.dtype),
              *(mla_layer[m] for m in _MLA_NAMES))
    mla_w = (normal(ka[8], (1, 1024, d)),)

    def mla_plain(dtype):
        def fn(x, *weights):
            lay = {m: w.astype(dtype) for m, w in zip(_MLA_NAMES, weights)}
            y = _rms_norm(x.astype(dtype), lay["ln1"], cfg.norm_eps)
            return (_latent(y, lay, cfg),)
        return fn

    # -- the held share of an expert layer under the group limit ------------
    T = 2048
    km = jax.random.split(jax.random.fold_in(key, 4), 9)
    weights = (
        normal(km[1], (d, E)) * d ** -0.5,                         # router
        matrix(km[2], (held_n, d, 2 * f)), matrix(km[3], (held_n, f, d)),
        matrix(km[7], (d, 2 * fs)), matrix(km[8], (fs, d)))
    moe_w = (normal(km[4], (T, d)),)
    # The held experts' group is made a little dearer so that a share of
    # the tokens keeps it and picks held experts there (a quarter of one
    # group of eight is held: unbiased, a token holds 0.25 assignments).
    bias = 0.1 * normal(km[5], (E,))
    bias = bias.at[first:first + held_n].add(0.15)

    def own_choice(x, bias):
        """(The k experts this file's router picks for each row, the rows
        whose pick a rounding could turn: the k-th and next biased score,
        or the last group kept and the first dropped, within 1e-4.)"""
        biased = _sigmoid(x.astype(f32) @ weights[0]) + bias
        g_ = biased.reshape(-1, cfg.n_group, E // cfg.n_group)
        marks = -jnp.sort(-jnp.sum(-jnp.sort(-g_, -1)[..., :2], -1), -1)
        tg = cfg.topk_group
        close = (marks[:, tg - 1] - marks[:, tg] < 1e-4) \
            if tg < cfg.n_group else jnp.zeros(marks.shape[0], bool)
        best, chosen = jax.lax.top_k(
            _narrowed(biased, cfg.n_group, cfg.topk_group), k + 1)
        return chosen[:, :k], close | (best[:, k - 1] - best[:, k] < 1e-4)

    def rows(kk, count):
        x = normal(kk, (count, d)).astype(cfg.dtype)
        return jnp.where(own_choice(x, bias)[1][:, None], 0, x)

    def moe_plain(dtype):
        def fn(x, router, gate_up, down, shared_gate_up, shared_down, bias,
               chosen):
            x, router, gate_up, down, shared_gate_up, shared_down, bias = (
                t.astype(dtype) for t in (x, router, gate_up, down,
                                          shared_gate_up, shared_down, bias))
            return (_plain_experts(
                x, router, bias, gate_up, down, shared_gate_up, shared_down,
                k=k, first=first, scale=cfg.routed_scale,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                chosen=chosen)[0],)
        return fn

    with jax.default_matmul_precision("highest"):
        rule_exact = tuple(t.astype(f32) for t in rule_in)
        rule_want = _rule_all_of(rule_plain(f32))(rule_w, *rule_exact)
        kda_exact = tuple(t.astype(f32) for t in kda_in)
        kda_want = _all_of(kda_plain(f32), len(kda_in))(kda_w, *kda_exact)
        mla_exact = tuple(t.astype(f32) for t in mla_in)
        mla_want = _all_of(mla_plain(f32), len(mla_in))(mla_w, *mla_exact)
        moe_in = (rows(km[0], T), *weights)
        moe_exact = tuple(t.astype(f32) for t in moe_in)
        moe_given = (moe_w, *moe_exact, bias,
                     own_choice(moe_in[0], bias)[0])
        moe_want = _all_of(moe_plain(f32), 6)(*moe_given)
    return dict(rule_in=rule_in, rule_w=rule_w, rule_exact=rule_exact,
                rule_want=rule_want, rule_plain=rule_plain,
                kda_in=kda_in, kda_w=kda_w, kda_exact=kda_exact,
                kda_want=kda_want, kda_plain=kda_plain,
                mla_in=mla_in, mla_w=mla_w, mla_exact=mla_exact,
                mla_want=mla_want, mla_plain=mla_plain,
                moe_in=moe_in, moe_w=moe_w, moe_given=moe_given,
                moe_want=moe_want, moe_plain=moe_plain, bias=bias)


GROUPS = ("rule", "kda", "mla", "moe")
_RULE_VALUES = ("o", "state", "dq", "dk", "dv", "dg", "dbeta", "dinit")


def kernel_errors(cfg, seed: int = 0, low: bool = False,
                  groups=None) -> dict:
    """What the program runs as its modules call it (on a TPU its
    kernels), against this file's float32 forms at the configuration's
    sizes, the root mean square of got - want over that of want, a value:

    * the normalisation and the delta rule as models.decoder calls them, on
      2,048 tokens from a seeded initial state, q, k, v silu of a normal in
      the model's dtype, beta = sigmoid(N(0, 1)) and g DRAWN OVER THE WHOLE
      OF (bound, 0), uniform a channel (a seeded start's gate sits near half
      the bound: the sub-blocks' bound is exercised here, not assumed): o,
      the final state and the gradient of a seeded weighted sum of both by
      q, k, v, g, beta and the initial state (`rule_*`);
    * a whole KDA layer on one sequence of 1,024 rows, from its input norm
      to W_o: the output and the gradient of a seeded weighted sum of it by
      the rows and every weight (`kda_*`);
    * a whole latent-attention layer with its head-wise gate on 1,024 rows
      the same way (`mla_*`);
    * the held share of an expert layer with its shared expert on 2,048
      seeded rows under THIS file's group-limited routing, which the
      program's own router has to arrive at (a row whose pick a rounding
      could turn is made a zero row first): the output and the gradient of
      a seeded weighted sum by the rows, the router, both expert tensors
      and both shared matrices (`moe_*`).

    With `low`, what is compared is this file's forms themselves with
    every input and value in bfloat16: the second reading KERNEL_LIMIT
    lies under. `groups` names the values wanted, by their prefix: all of
    GROUPS, or under a planted fault the one group that fault moves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    groups = groups or tuple(_PLANTED_GROUP) or GROUPS
    case = _cases(cfg, seed)
    bf16, dec = jnp.bfloat16, cfg.decoder()
    H, K = cfg.n_heads, cfg.kda_head_dim
    layer_sizes = dict(experts_per_token=cfg.experts_per_token,
                       first=cfg.held[0], routed_scale=cfg.routed_scale,
                       weight_eps=_WEIGHT_EPS, gated=True,
                       n_group=cfg.n_group, topk_group=cfg.topk_group)

    def rule_program(q, k, v, g, beta, init):
        return decoder.kda_rule(
            decoder._unit_heads(q, H, K ** -0.5, cfg.norm_eps),
            decoder._unit_heads(k, H, 1.0, cfg.norm_eps), v, g, beta,
            cfg.kda_chunk, init, cfg.kda_lower_bound)

    def kda_program(x, *weights):
        return (decoder.kda(x, dict(zip(_KDA_NAMES, weights)), dec)[0],)

    def mla_program(x, *weights):
        return (decoder.latent_attention(
            x, dict(zip(_MLA_NAMES, weights)), dec)[0],)

    def moe_program(x, router, gate_up, down, shared_gate_up, shared_down,
                    bias):
        return (decoder.held_moe_layer(x, router, bias, gate_up, down,
                                       shared_gate_up, shared_down,
                                       **layer_sizes)[0],)

    def highest(fn, *given):
        with jax.default_matmul_precision("highest"):
            return fn(*given)

    errors = {}
    if "rule" in groups:
        got = highest(_rule_all_of(case["rule_plain"](bf16)), case["rule_w"],
                      *case["rule_exact"]) if low \
            else _rule_all_of(rule_program)(case["rule_w"], *case["rule_in"])
        errors.update(zip((f"rule_{n}" for n in _RULE_VALUES),
                          _rel(got, case["rule_want"])))
    for prefix, names, program in (("kda", _KDA_NAMES, kda_program),
                                   ("mla", _MLA_NAMES, mla_program)):
        if prefix not in groups:
            continue
        n = len(names) + 1
        got = highest(_all_of(case[prefix + "_plain"](bf16), n),
                      case[prefix + "_w"], *case[prefix + "_exact"]) if low \
            else _all_of(program, n)(case[prefix + "_w"],
                                     *case[prefix + "_in"])
        values = ("out", "dx", *("d" + m for m in names))
        errors.update(zip((f"{prefix}_{v}" for v in values),
                          _rel(got, case[prefix + "_want"])))
    if "moe" in groups:
        got = highest(_all_of(case["moe_plain"](bf16), 6),
                      *case["moe_given"]) if low \
            else _all_of(moe_program, 6)(case["moe_w"], *case["moe_in"],
                                         case["bias"])
        errors.update(zip(
            ("moe_out", "moe_dx", "moe_drouter", "moe_dgate_up", "moe_ddown",
             "moe_dshared_gate_up", "moe_dshared_down"),
            _rel(got, case["moe_want"])))
    return errors
