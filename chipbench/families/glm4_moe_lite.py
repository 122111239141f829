"""glm4_moe_lite family (ray_tpu.models.glm4_moe_lite): config builder,
operation and byte counts, and a plain float32 reference of GLM-4.7-Flash's
layer equations (zai-org/GLM-4.7-Flash config.json, model_type
glm4_moe_lite). The model's own modeling file is not on this machine:
attention, router, experts and the multi-token-prediction module are
DeepSeek-V3's (arXiv:2412.19437 and the public modeling_deepseek.py), which
the config's keys name one for one.

The equations (d = 2048, eps 1e-5, no bias on any matrix; T tokens; H = 20
heads, 192 no-rope + 64 rope query and key columns, 256 value columns a
head, query latent 768, key latent 512):

    x_0 = E[token];  layer l:  x <- x + mla(rmsnorm(x; w1));
                               x <- x + F(rmsnorm(x; w2));  h = x_L
    logits = rmsnorm(h; w_f) Head                          (Head untied)
    mla        c_q = rmsnorm(y W_qa; [768]);  q = c_q W_qb -> a head
               [q_n | q_r];  [c | k_r] = y W_kva;  c^ = rmsnorm(c; [512])
               (both latents' norms at eps 1e-6);  a head's [k_n | v] = c^
               W_kvb;  q_r, k_r rotated over their 64 columns (k_r ONE key
               a token under all 20 heads) at f_i = 1e6^(-2i/64), HF's
               rotate_half form;
               out = concat_h(causal softmax(q k^T 256^(-1/2)) v) W_o
    F = dense (l < first_k_dense_replace)
               W2 (silu(W1 y) * W3 y), width 10,240
    F = experts
               s = sigmoid(y W_r) in R^64, float32; the 4 experts of a
               token are the top 4 of s + b (b the selection bias: it picks
               and never weighs; in the loss, a training step's, b has
               first moved `bias_rounds` rounds of its rule on the batch's
               own s: `_bias_moved`); w_j = 1.8 s[e_j] / (sum_j s[e_j] +
               1e-20);
               out = sum over the HELD e_j of w_j W2[e_j] (silu(W1[e_j] y)
                     * W3[e_j] y), width 1,536
                     + Ws2 (silu(Ws1 y) * Ws3 y), the shared expert, 1,536
    the prediction module (DeepSeek-V3 section 2.2), t_{i+1} the next token:
               u_i = [rmsnorm(E[t_{i+1}]; w_e) ; rmsnorm(h_i; w_h)] W_eh
               u' = one more expert-layer block over u, causal over the
               same positions, its own weights, router and bias
               logits'_i = rmsnorm(u'_i; w_m) Head      (E, Head the main's)
    loss = CE(logits, t_{i+1}) + 0.3 CE(logits', t_{i+2}), the second over
           positions 0..S-2; no balance loss (noaux_tc)

One chip's share: the file's `n_routed_experts` experts from the first on
are held, in the module's block as in the layers'; what the absent ones
would add is left out, here as in the program; the shared expert is whole.
The vocabulary is the file's slice.

The reference runs attention as a plain masked softmax over per-head keys
and values in query blocks and EVERY held expert for every token masked by
the reference's own routing: no latent cache, no sort, no grouped matmul,
no kernel, and no code shared with ray_tpu. It reads the program's
parameter tree (`mtp` = {enorm, hnorm, w_eh [embedding rows | hidden rows,
d], block, norm}). The forms every DeepSeek-V3 reference here shares (the
latent attention, the dense and the gated experts, the rotary) are
families/xing4.py's and lfm2_moe.py's. The count functions take the
program's config object or the configuration file's dict and import no jax:
per-layer readers call them in run.py's parent process, which must never
initialise a backend."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.machinery
import importlib.util

# A tree from before the family says so as the cell is looked up, in
# run.py's own process, before a cluster or a chip is touched
# (families/granite_hybrid.py has why it is looked for this way).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.glm4_moe_lite", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models."
                      "glm4_moe_lite: it cannot run a glm4_moe_lite "
                      "configuration")

from .lfm2_moe import _blocks, _rms_norm, _sigmoid  # noqa: E402
from .xing4 import (_all_of, _dense, _experts, _Given, _mla,  # noqa: E402
                    _plain_experts, _rel, _scale_left_out)

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three, ops/grouped_matmul.py's two (three scopes).
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_gmm_kernel", "_tgmm_kernel")

_LOSS_ROWS = 2048
_LATENT_NORM_EPS = 1e-6         # the family code's default for both latents
_WEIGHT_EPS = 1e-20


def build(config: dict, **overrides):
    """The program's Glm4MoeLiteConfig at the file's sizes."""
    from ray_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("partial_rotary_factor", 1),
                      ("num_key_value_heads", config["num_attention_heads"])):
        if config[key] != want:
            raise ValueError(f"models/glm4_moe_lite.py has {key} = {want!r} "
                             f"only, not {config[key]!r}")
    a, sizes = config["assumed"], config["deployment_sizes"]
    if a["latent_norm_eps"] != _LATENT_NORM_EPS:
        raise ValueError("models/decoder.py norms a latent at eps 1e-6")
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              qk_nope_head_dim=config["qk_nope_head_dim"],
              qk_rope_head_dim=config["qk_rope_head_dim"],
              v_head_dim=config["v_head_dim"],
              q_lora_rank=config["q_lora_rank"],
              kv_lora_rank=config["kv_lora_rank"],
              n_layers=config["num_hidden_layers"],
              n_dense_layers=config["first_k_dense_replace"],
              d_ff=config["intermediate_size"],
              n_experts=sizes["n_routed_experts"],
              experts_held=(sizes["first_expert_held"],
                            config["n_routed_experts"]),
              experts_per_token=config["num_experts_per_tok"],
              d_expert=config["moe_intermediate_size"],
              n_shared_experts=config["n_shared_experts"],
              routed_scale=float(config["routed_scaling_factor"]),
              n_predict_layers=config["num_nextn_predict_layers"],
              mtp_loss_weight=a["mtp_loss_weight"],
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["rms_norm_eps"],
              init_std=a["initializer_range"],
              bias_rounds=a["bias_rounds"],
              balance_tokens=a["balance_tokens"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    return Glm4MoeLiteConfig(**kw)


# The cell's second limit, on what this configuration brought: the largest
# of kernel_errors' 32 relative errors, each the root mean square of got -
# want over that of want. Read on the v5e at the published sizes on 12 seeds
# (chipbench/limit_readings.py, my chip runs, PR 55; PERF.md section 4): the
# program 0.007495 to 0.007673 (the cell's own seed 0: 0.007583), this
# file's forms with every input and value in bfloat16, the nearest precision
# below, 0.011005 to 0.011302 (seed 0: 0.011165); in all 24 readings the
# worst value is a gradient of the latent layer's query path (W_qa or W_qb),
# as in families/xing4.py's. 1.43 times apart at the nearest (1.47 on seed
# 0); the limit is their geometric mean: 1.199 times over the program's
# largest reading and 1.196 under the lower precision's smallest over the 12
# seeds, 1.21 either side on seed 0, the one hold_kernels reads, where a
# reading moves 1.2% and 1.4% between seeds. (ISSUE 55 asked for 1.2 times
# either side: the two precisions are no further apart on this layer.) By
# group: the module end to end reads 0.0062 to 0.0063 and its all-bfloat16
# form 0.0080 to 0.0081, the expert layer 0.0039 to 0.0042 and 0.0058 to
# 0.0059: under this limit, so the lower precision is caught by the latent
# layer's values. Each of the eleven structural faults below reads 0.144 or
# more on both seeds read (the module's block not causal 0.144 to 0.146,
# the scale of the no-rope width 0.292 to 0.295, the target not shifted
# 0.406 to 0.408, the factor 1.8 left out 0.444 to 0.445, rope on no-rope
# columns 0.505 to 0.509, the loss's weight at 1 0.672 to 0.675, hnorm,
# enorm or the table's gradient left out 1.0, the embedding of this token
# 1.42 to 1.43, the halves swapped 1.41 to 1.46).
KERNEL_LIMIT = 0.0092


def hold_kernels(cfg):
    """Refuse a program whose latent-attention layer at 256 | 256,
    prediction module (from the hidden rows and the next tokens' table
    rows to the step's two losses) or held gated-expert layer is further
    from this file's float32 forms than KERNEL_LIMIT: the loss at
    initialisation, which drivers/train.py compares, hardly sees a layer's
    structure (PERF.md section 4), so the cell holds what this
    configuration brought to a limit of its own before it hands the
    program over."""
    from .. import harness

    errors = kernel_errors(cfg)
    _cases.cache_clear()        # its arrays are the chip's, and the step's now
    worst = max(errors, key=errors.get)
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program is off the float32 reference by {errors[worst]:.3g} "
        f"of the root mean square of {worst} (limit {KERNEL_LIMIT}): {errors}")


# The family's learning rate, for every cell of it (ISSUE 55 names it):
# families/xing4.py LEARNING_RATE has why a stack whose every sequence mixer
# is attention over 16,384 keys does not start at the other families' 3e-4.
# AdamW, weight decay 0.01, no schedule. drivers/train.py takes the step
# this file hands it, so the rate lives here and the configuration's
# `assumed.optimizer` states it; a traffic mix's `optimizer` is prose.
LEARNING_RATE = 1e-4


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    the layers held to KERNEL_LIMIT first where the kernels are the
    chip's (elsewhere tier-1 holds them to the reference). `loss` is the
    step's own sum L."""
    import jax
    import optax

    from ray_tpu.models import glm4_moe_lite as program

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = program.make_glm4_moe_lite_train_step(
        cfg, optimizer=optax.adamw(LEARNING_RATE, weight_decay=0.01),
        mesh=mesh, rules=rules)
    return (lambda key: program.glm4_moe_lite_init(key, cfg), init_state,
            step, lambda params, batch: program.glm4_moe_lite_loss(
                params, batch, _as_planted(cfg)))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
# A fault of a number the program's config carries: `planted` sets it here
# and whatever this file hands the program reads its config through
# `_as_planted`.
_CONFIG_FAULT = {}


def _as_planted(cfg):
    return dataclasses.replace(cfg, **_CONFIG_FAULT) if _CONFIG_FAULT else cfg


def _sizes_of_decoder(layer, dec) -> _Given:
    """What `_mla` reads, off a layer's weights and the program's Decoder
    (a planted plain form is handed those)."""
    c = layer["latent_norm"].shape[0]
    r = layer["w_kva"].shape[1] - c
    n = layer["w_qb"].shape[1] // dec.n_heads - r
    vd = layer["w_kvb"].shape[1] // dec.n_heads - n
    return _Given(dec.n_heads, c, r, n, vd, dec.rope_base,
                  tuple(dec.rope_base ** (-2.0 * i / r)
                        for i in range(r // 2)), dec.sm_scale)


def _rope_on_the_no_rope_columns(mixer, x, layer, dec, cache=None,
                                 start_pos=None):
    """The latent-attention mixer as the plain form in the program's own
    precision, the first 64 no-rope columns turned too."""
    y = _rms_norm(x.astype("float32"), layer["ln1"],
                  dec.norm_eps).astype(x.dtype)
    return _mla(y, layer, _sizes_of_decoder(layer, dec),
                "rope_on_the_no_rope_columns").astype(x.dtype), None


def _scale_of_the_no_rope_width(mixer, x, layer, dec, cache=None,
                                start_pos=None):
    """Scores over sqrt(192), the no-rope columns', not sqrt(192 + 64)."""
    n = _sizes_of_decoder(layer, dec).n
    return mixer(x, layer, dec._replace(sm_scale=n ** -0.5), cache, start_pos)


def _module_by_plain_form(fault: str):
    """The prediction module's own lines (two norms, the concatenation, the
    projection, the last norm) in the program's precision with one of them
    wrong; the block is the program's."""
    def faulty(module_fn, h, embedded, module, block, eps):
        import jax.numpy as jnp

        from ray_tpu.models import decoder

        def norm(t, name):
            return _rms_norm(t.astype(jnp.float32), module[name],
                             eps).astype(t.dtype)

        e = embedded if fault == "enorm_left_out" else norm(embedded, "enorm")
        g = h if fault == "hnorm_left_out" else norm(h, "hnorm")
        pair = [g, e] if fault == "halves_swapped" else [e, g]
        u = jnp.concatenate(pair, -1) @ module["w_eh"]
        u, stats, _, _ = block(u, module["block"], None, None,
                               decoder.Shared())
        return norm(u, "norm"), stats
    return faulty


def _embedding_of_this_token(module_fn, h, embedded, module, block, eps):
    """E[t_i] beside h_i, not E[t_{i+1}] (position 0 gets the last row)."""
    import jax.numpy as jnp
    return module_fn(h, jnp.roll(embedded, 1, axis=1), module, block, eps)


def _table_gradient_dropped(module_fn, h, embedded, module, block, eps):
    """The module's lookup gives the table no gradient."""
    import jax
    return module_fn(h, jax.lax.stop_gradient(embedded), module, block, eps)


def _block_not_causal(module_fn, h, embedded, module, block, eps):
    """The module's block attends over every position, later ones too."""
    from ray_tpu.models import decoder

    real = decoder.flash_attention
    decoder.flash_attention = lambda q, k, v, causal, *rest: real(
        q, k, v, False, *rest)
    try:
        return module_fn(h, embedded, module, block, eps)
    finally:
        decoder.flash_attention = real


def _target_not_shifted(next_targets, targets):
    """The module scored on t_{i+1}, the main model's own target."""
    return targets, next_targets(targets)[1]


# What limit_readings.py plants in the program, one at a time, each a fault
# of structure in what this configuration brought: (the module and the name
# on it that stands for the faulty one meanwhile, the faulty one given the
# real one first), or a number of the program's config.
_DECODER, _PROGRAM = "ray_tpu.models.decoder", "ray_tpu.models.glm4_moe_lite"
# (and which of kernel_errors' groups a fault moves, by its name's place:
# under a planted fault kernel_errors reads that group alone)
_GROUP_OF_FAULT = {"scale_of_the_no_rope_width": "mla",
                   "rope_on_the_no_rope_columns": "mla",
                   "routed_scale_left_out": "moe"}    # any other: "mtp"
_PLANTED_GROUP = []
STRUCTURAL_FAULTS = {
    "halves_swapped": (_DECODER, "prediction_module",
                       _module_by_plain_form("halves_swapped")),
    "hnorm_left_out": (_DECODER, "prediction_module",
                       _module_by_plain_form("hnorm_left_out")),
    "enorm_left_out": (_DECODER, "prediction_module",
                       _module_by_plain_form("enorm_left_out")),
    "embedding_of_this_token": (_DECODER, "prediction_module",
                                _embedding_of_this_token),
    "target_not_shifted": (_PROGRAM, "_next_targets", _target_not_shifted),
    "mtp_loss_weight_1": {"mtp_loss_weight": 1.0},
    "block_not_causal": (_DECODER, "prediction_module", _block_not_causal),
    "table_gradient_dropped": (_DECODER, "prediction_module",
                               _table_gradient_dropped),
    "scale_of_the_no_rope_width": (_DECODER, "latent_attention",
                                   _scale_of_the_no_rope_width),
    "rope_on_the_no_rope_columns": (_DECODER, "latent_attention",
                                    _rope_on_the_no_rope_columns),
    "routed_scale_left_out": (_DECODER, "held_moe_layer", _scale_left_out),
}
PRECISION_FAULTS = {}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault`: the program calls the latent mixer, the
    prediction module, the expert layer and the module's targets through
    its modules' own names, one of which stands for the faulty one
    meanwhile; a fault of a config's number is read by what this file
    hands the program (`_as_planted`). Trace the program inside; a
    function jitted before keeps what it traced."""
    what = STRUCTURAL_FAULTS[fault]
    _PLANTED_GROUP[:] = [_GROUP_OF_FAULT.get(fault, "mtp")]
    try:
        if isinstance(what, dict):
            _CONFIG_FAULT.update(what)
            yield
            return
        where, name, faulty = what
        module = importlib.import_module(where)
        real = getattr(module, name)
        setattr(module, name, functools.partial(faulty, real))
        try:
            yield
        finally:
            setattr(module, name, real)
    finally:
        _CONFIG_FAULT.clear()
        _PLANTED_GROUP.clear()


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's Glm4MoeLiteConfig or the configuration's
    dict. `held` experts of `e` the router spans; `modules` prediction
    modules, each one more attention call, expert layer and head pass."""
    if isinstance(cfg, dict):
        layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        return dict(
            d=cfg["hidden_size"], v=cfg["vocab_size"],
            h=cfg["num_attention_heads"], n=cfg["qk_nope_head_dim"],
            r=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
            cq=cfg["q_lora_rank"], c=cfg["kv_lora_rank"],
            ff=cfg["intermediate_size"],
            e=cfg["deployment_sizes"]["n_routed_experts"],
            held=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
            f=cfg["moe_intermediate_size"],
            fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            layers=layers, dense_layers=dense, expert_layers=layers - dense,
            modules=cfg["num_nextn_predict_layers"])
    return dict(
        d=cfg.d_model, v=cfg.vocab_size, h=cfg.n_heads,
        n=cfg.qk_nope_head_dim, r=cfg.qk_rope_head_dim, vd=cfg.v_head_dim,
        cq=cfg.q_lora_rank, c=cfg.kv_lora_rank, ff=cfg.d_ff, e=cfg.n_experts,
        held=cfg.held[1], k=cfg.experts_per_token, f=cfg.d_expert,
        fs=cfg.d_shared, layers=cfg.n_layers,
        dense_layers=cfg.n_dense_layers,
        expert_layers=cfg.n_layers - cfg.n_dense_layers,
        modules=cfg.n_predict_layers)


def _held_rows(s: dict, tokens: int) -> float:
    """Rows a layer's held experts see under a balanced router: every
    token's k assignments fall evenly on the e experts."""
    return tokens * s["k"] * s["held"] / s["e"]


def held_rows_balanced(cfg, tokens: int) -> float:
    """The rows a layer's held experts see a step of `tokens` under a
    balanced router: what the counts below take the routed work to be, and
    what the step's `expert_rows_held` is read against
    (chipbench/step_counters.py), the module's row as the layers'."""
    return _held_rows(_dims(cfg), tokens)


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul operations one token needs in the forward pass at context
    `seq`. Latent attention: W_qa, W_qb, W_kva, W_kvb and W_o and causal
    attention (QK^T over n + r columns and PV over vd, half the square). A
    dense layer's three matrices. An expert layer: the router over all e
    outputs, the shared expert's three matrices and the BALANCED share of
    the routed work (k held / e assignments a token, three matmuls each);
    nothing made again. The untied head once. A prediction module: its
    projection W_eh [2 d, d], one more attention and expert layer and the
    head a second time (the module adds work a token, no tokens)."""
    s = _dims(cfg)
    d, h, qk = s["d"], s["h"], s["n"] + s["r"]
    attention = (2 * d * s["cq"] + 2 * s["cq"] * h * qk
                 + 2 * d * (s["c"] + s["r"])
                 + 2 * s["c"] * h * (s["n"] + s["vd"]) + 2 * h * s["vd"] * d
                 + 2 * seq * h * (qk + s["vd"]) / 2)
    dense = 3 * 2 * d * s["ff"]
    experts = (2 * d * s["e"] + 3 * 2 * d * s["fs"]
               + _held_rows(s, 1) * 3 * 2 * d * s["f"])
    head = 2 * d * s["v"]
    module = 2 * (2 * d) * d + attention + experts + head
    return (s["layers"] * attention + s["dense_layers"] * dense
            + s["expert_layers"] * experts + head + s["modules"] * module)


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the kernels' tiles made again in their backward) is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step, the
    layers' calls and the module's: forward QK^T (n + r wide) and PV (vd
    wide); backward dV and dP (vd wide), dQ and dK (n + r wide); each
    2*B*H*S*S*width, halved for the causal mask."""
    s = _dims(cfg)
    qk, vd = s["n"] + s["r"], s["vd"]
    return ((s["layers"] + s["modules"]) * 2 * batch * seq * seq * s["h"]
            * ((qk + vd) + 2 * (qk + vd)) / 2)


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv, k and v
    at the 20 heads the kernels are handed (the shared key under every
    head). bf16."""
    s = _dims(cfg)
    wide = batch * seq * s["h"] * (s["n"] + s["r"]) * 2
    narrow = batch * seq * s["h"] * s["vd"] * 2
    return (s["layers"] + s["modules"]) * (
        (2 * wide + 2 * narrow) + (4 * wide + 4 * narrow))


def expert_matmul_flops(cfg, tokens: int) -> float:
    """Required operations of the grouped matmuls in one train step, the
    expert layers' and the module's block's, for a BALANCED router: the
    held experts' rows (tokens x k x held / e a layer) go through three
    matmuls forward (gate, up, down; gate and up are one grouped matmul of
    twice the width) and six backward, 2 * rows * d * f each. What remat
    makes again is not counted; the shared expert is no grouped matmul."""
    s = _dims(cfg)
    return ((s["expert_layers"] + s["modules"]) * (3 + 6) * 2.0
            * _held_rows(s, tokens) * s["d"] * s["f"])


def expert_matmul_bytes(cfg, tokens: int) -> float:
    """Least HBM traffic of those nine matmuls a layer: each touches its
    rows [rows, d], the held experts' tensor [held, d, f] and its other
    rows [rows, f] once. bf16."""
    s = _dims(cfg)
    one = (_held_rows(s, tokens) * (s["d"] + s["f"])
           + s["held"] * s["d"] * s["f"])
    return (s["expert_layers"] + s["modules"]) * (3 + 6) * 2.0 * one


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _sizes_of(cfg) -> _Given:
    """What `_mla` reads of a configuration: heads, the key latent's, the
    rope key's, a head's no-rope and a head's value width, the rotary base,
    its 32 pairs' frequencies base^(-2i / r) and the scale (n + r)^(-1/2)."""
    r = cfg.qk_rope_head_dim
    return _Given(cfg.n_heads, cfg.kv_lora_rank, r, cfg.qk_nope_head_dim,
                  cfg.v_head_dim, cfg.rope_theta,
                  tuple(cfg.rope_theta ** (-2.0 * i / r)
                        for i in range(r // 2)),
                  (cfg.qk_nope_head_dim + r) ** -0.5)


def _layer(x, lay, cfg, dense: bool, rounds: int = 0):
    """One pre-norm block on the one residual stream."""
    x = x + _mla(_rms_norm(x, lay["ln1"], cfg.norm_eps), lay, _sizes_of(cfg))
    y = _rms_norm(x, lay["ln2"], cfg.norm_eps)
    return x + (_dense(y, lay) if dense else _experts(y, lay, cfg, rounds))


def _module(h, embedded, mod, cfg, rounds: int = 0):
    """The prediction module: h [b, s, d] the last block's output before
    the final norm, `embedded` [b, s, d] the main table's rows of the next
    tokens -> the rows the main head reads."""
    import jax.numpy as jnp

    u = jnp.concatenate([_rms_norm(embedded, mod["enorm"], cfg.norm_eps),
                         _rms_norm(h, mod["hnorm"], cfg.norm_eps)],
                        -1) @ mod["w_eh"]
    return _rms_norm(_layer(u, mod["block"], cfg, False, rounds),
                     mod["norm"], cfg.norm_eps)


def _stack(p, tokens, cfg, rounds: int = 0):
    """The last block's output [b, s, d], before the final norm."""
    x = p["embed"][tokens]
    for i, lay in enumerate(p["layers"]):
        x = _layer(x, lay, cfg, i < cfg.n_dense_layers, rounds)
    return x


def _as(params, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)


def reference_logits(params, tokens, cfg):
    """Full forward of the main stack in float32: tokens [b, s] -> logits
    [b, s, vocab]. Call under jax.default_matmul_precision("highest")."""
    p = _as(params, None)
    return _rms_norm(_stack(p, tokens, cfg), p["lnf"],
                     cfg.norm_eps) @ p["head"]


def reference_module_logits(params, tokens, next_tokens, cfg):
    """The prediction module's logits [b, s, vocab] in float32, of the
    token two on from each position."""
    p = _as(params, None)
    return _module(_stack(p, tokens, cfg), p["embed"][next_tokens], p["mtp"],
                   cfg) @ p["head"]


def _mean_cross_entropy(x, head, targets, valid=None):
    """Mean over the rows `valid` marks (all with none) of -log
    softmax(x head)[target], the logits a block of rows at a time."""
    import jax
    import jax.numpy as jnp

    rows = x.reshape(-1, x.shape[-1])
    counted = jnp.ones(rows.shape[0], bool) if valid is None \
        else valid.reshape(-1)
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb, vb = args
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), -1)
        return jnp.sum(jnp.where(
            vb, jnp.take_along_axis(logp, tb[:, None], -1)[:, 0], 0.0))

    total = jax.lax.map(one_block, (
        rows.reshape(-1, block, rows.shape[-1]),
        targets.reshape(-1, block), counted.reshape(-1, block)))
    return -jnp.sum(total) / jnp.sum(counted)


def _two_losses(x, x_next, head, targets, weight):
    """L = CE(x head, t_{i+1}) + weight CE(x_next head, t_{i+2}), the
    second over all positions but the last: `targets` [b, s] holds t_{i+1}
    at position i, so t_{i+2} is the row one to the right."""
    import jax.numpy as jnp

    s = targets.shape[1]
    two_on = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
    has_one = jnp.broadcast_to(jnp.arange(s) < s - 1, targets.shape)
    return _mean_cross_entropy(x, head, targets) + weight * \
        _mean_cross_entropy(x_next, head, two_on, has_one)


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """The training loss L of a training step's forward (each selection
    bias, the module's too, moved `cfg.bias_rounds` rounds on the batch
    first), in float32, the logits a block of rows at a time; the main
    cross entropy alone where the model has no module. `dtype` is for
    setting the comparison's limit only: the same reference with every
    parameter and value in a lower precision (bfloat16) has to come out as
    not correct (PERF.md)."""
    p = _as(params, dtype)
    h = _stack(p, tokens, cfg, cfg.bias_rounds)
    x = _rms_norm(h, p["lnf"], cfg.norm_eps)
    if "mtp" not in p:
        return _mean_cross_entropy(x, p["head"], targets)
    x_next = _module(h, p["embed"][targets], p["mtp"], cfg, cfg.bias_rounds)
    return _two_losses(x, x_next, p["head"], targets, cfg.mtp_loss_weight)


# ---------------------------------------------------------------------------
# the layers this configuration brought, against the forms above
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def _cases(cfg, seed: int, long: int) -> dict:
    """kernel_errors' seeded inputs and what this file's float32 forms give
    on them, once a (configuration, seed): the program, the all-bfloat16
    forms and every planted fault are read against the same values."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d, E, k, V = cfg.d_model, cfg.n_experts, cfg.experts_per_token, \
        cfg.vocab_size
    first, held = cfg.held
    f, fs = cfg.d_expert, cfg.d_shared
    T = 2048
    normal = jax.random.normal
    key = jax.random.PRNGKey(seed)

    def matrix(kk, shape, dtype=cfg.dtype):
        return (normal(kk, shape) * shape[-2] ** -0.5).astype(dtype)

    def scale(kk, width):
        return 1.0 + 0.1 * normal(kk, (width,))

    # -- a latent-attention layer -------------------------------------------
    ka = jax.random.split(jax.random.fold_in(key, 1), 9)
    sizes = _sizes_of(cfg)
    h, c, r, n, vd, cq = (sizes.h, sizes.c, sizes.r, sizes.n, sizes.vd,
                          cfg.q_lora_rank)
    rows_a = 1024                       # the flash kernels' whole blocks
    mla_shapes = ((d, cq), (cq, h * (n + r)), (d, c + r), (c, h * (n + vd)),
                  (h * vd, d))
    mla_names = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")

    def attention_weights(keys):
        """A latent layer's three norms and five matrices."""
        return {"ln1": scale(keys[6], d), "q_latent_norm": scale(keys[7], cq),
                "latent_norm": scale(keys[8], c),
                **{name: matrix(kk, shape) for name, kk, shape in zip(
                    mla_names, keys[1:6], mla_shapes)}}

    norms = {name: w for name, w in attention_weights(ka).items()
             if name not in mla_names}
    mla_in = (normal(ka[0], (1, rows_a, d)).astype(cfg.dtype),
              *(matrix(kk, shape) for kk, shape in zip(ka[1:6], mla_shapes)))
    mla_w = (normal(jax.random.fold_in(key, 4), (1, rows_a, d)),)

    def as_layer(*matrices):
        return {**norms, **dict(zip(mla_names, matrices))}

    def mla_plain(dtype):
        def fn(x, *matrices):
            lay = {name: m.astype(dtype)
                   for name, m in as_layer(*matrices).items()}
            y = _rms_norm(x.astype(dtype), lay["ln1"], cfg.norm_eps)
            return (_mla(y, lay, sizes),)
        return fn

    # -- the held share of an expert layer and its shared expert ------------
    km = jax.random.split(jax.random.fold_in(key, 3), 9)
    weights = (
        normal(km[1], (d, E)) * d ** -0.5,                         # router
        matrix(km[2], (held, d, 2 * f)), matrix(km[3], (held, f, d)),
        matrix(km[7], (d, 2 * fs)), matrix(km[8], (fs, d)))
    moe_w = (normal(km[4], (T, d)),)
    bias = 0.1 * normal(km[5], (E,))

    def own_choice(x, bias):
        """(The k experts this file's router picks for each row, the rows
        whose pick a rounding could turn.)"""
        best, chosen = jax.lax.top_k(
            _sigmoid(x.astype(f32) @ weights[0]) + bias, k + 1)
        return chosen[:, :k], best[:, k - 1] - best[:, k] < 1e-4

    def rows(kk, count):
        x = normal(kk, (count, d)).astype(cfg.dtype)
        return jnp.where(own_choice(x, bias)[1][:, None], 0, x)

    def moe_plain(dtype):
        def fn(x, router, gate_up, down, shared_gate_up, shared_down, bias,
               chosen):
            x, router, gate_up, down, shared_gate_up, shared_down, bias = (
                t.astype(dtype) for t in (x, router, gate_up, down,
                                          shared_gate_up, shared_down, bias))
            return (_plain_experts(x, router, bias, gate_up, down,
                                   shared_gate_up, shared_down, k=k,
                                   first=first, scale=cfg.routed_scale,
                                   chosen=chosen)[0],)
        return fn

    # two skewed routings: a bias no score outweighs on the first k held
    # experts (every assignment held: several passes where a quarter of the
    # experts are held); on k absent ones (none held: no pass, the shared
    # expert alone). Where every expert is held the second is the first.
    absent = [e for e in range(E) if not first <= e < first + held]
    all_held = jnp.zeros((E,)).at[first:first + k].set(10.0)
    biases = {
        "": bias, "all_held_": all_held,
        "none_held": jnp.zeros((E,)).at[
            jnp.array((absent or list(range(k)))[:k])].set(10.0)}

    # -- the prediction module, end to end ----------------------------------
    # From the last block's output and the next tokens' table rows to the
    # step's two losses over one head. Its block's experts are routed by
    # the bias alone (the first k held ones for every row: a pick that
    # hangs on a rounding of the attention before it would move a row's
    # output by itself), their weights still the scores'.
    kp = jax.random.split(jax.random.fold_in(key, 5), 12)
    kb = jax.random.split(jax.random.fold_in(key, 6), 9)
    kx = jax.random.split(jax.random.fold_in(key, 7), 6)
    block = {**attention_weights(kb), "ln2": scale(kx[0], d),
             "router": normal(kx[1], (d, E)) * d ** -0.5,
             "router_bias": all_held,
             "expert_gate_up": matrix(kx[2], (held, d, 2 * f)),
             "expert_down": matrix(kx[3], (held, f, d)),
             "shared_gate_up": matrix(kx[4], (d, 2 * fs)),
             "shared_down": matrix(kx[5], (fs, d))}
    targets = jax.random.randint(kp[0], (1, T), 0, V)
    mtp_in = (normal(kp[1], (1, T, d)).astype(cfg.dtype),          # h
              normal(kp[2], (1, T, d)).astype(cfg.dtype),          # E[t+1]
              matrix(kp[3], (d, V)),                               # the head
              matrix(kp[4], (2 * d, d)),                           # W_eh
              scale(kp[5], d), scale(kp[6], d), scale(kp[7], d))
    # the stack's final-norm rows, for the first of the two losses
    x_main = normal(kp[8], (1, T, d)).astype(cfg.dtype)
    # the loss counts as much as a row of the rows does
    mtp_w = (normal(kp[9], (1, T, d)), jnp.asarray(float(T)))

    # (the block, the stack's rows and the targets are handed to the
    # programs, not closed over: 340 MB of constants in an executable are
    # compiled again every run, the persistent cache holds 192 MiB)
    mtp_given = (block, x_main, targets)

    def as_module(w_eh, enorm, hnorm, norm, block):
        return {"enorm": enorm, "hnorm": hnorm, "w_eh": w_eh, "block": block,
                "norm": norm}

    def mtp_plain(dtype):
        def fn(h_, emb, head, w_eh, enorm, hnorm, norm, block, x_main,
               targets):
            mod = _as(as_module(w_eh, enorm, hnorm, norm, block), dtype)
            out = _module(h_.astype(dtype), emb.astype(dtype), mod, cfg)
            return out, _two_losses(x_main.astype(dtype), out,
                                    head.astype(dtype), targets,
                                    cfg.mtp_loss_weight)
        return fn

    with jax.default_matmul_precision("highest"):
        moe_in = (rows(km[0], T), *weights)
        exact = tuple(t.astype(f32) for t in moe_in)
        # (inputs, the float32 operands with the routing, what they give)
        with_gradients = {}
        for name in ("", "all_held_"):
            given = (moe_w, *exact, biases[name],
                     own_choice(moe_in[0], biases[name])[0])
            with_gradients[name] = (given, _all_of(moe_plain(f32), 6)(*given))
        forward = {}
        for name, x, b_ in (("out_long", rows(km[6], long), bias),
                            ("none_held", moe_in[0], biases["none_held"])):
            given = (x.astype(f32), *exact[1:], b_, own_choice(x, b_)[0])
            forward[name] = (x, b_, given, jax.jit(moe_plain(f32))(*given))
        mla_exact = tuple(t.astype(f32) for t in mla_in)
        mla_want = _all_of(mla_plain(f32), 6)(mla_w, *mla_exact)
        mtp_exact = tuple(t.astype(f32) for t in mtp_in)
        mtp_want = _all_of(mtp_plain(f32), 7)(mtp_w, *mtp_exact, *mtp_given)
    return dict(as_layer=as_layer, mla_plain=mla_plain, mla_in=mla_in,
                mla_w=mla_w, mla_exact=mla_exact, mla_want=mla_want,
                as_module=as_module, mtp_plain=mtp_plain, mtp_in=mtp_in,
                mtp_w=mtp_w, mtp_exact=mtp_exact, mtp_want=mtp_want,
                mtp_given=mtp_given,
                moe_plain=moe_plain, moe_in=moe_in, moe_w=moe_w,
                weights=weights, biases=biases,
                with_gradients=with_gradients, forward=forward)


GROUPS = ("mla", "mtp", "moe")


def kernel_errors(cfg, seed: int = 0, low: bool = False,
                  long: int = 16384, groups=None) -> dict:
    """What the program runs as its modules call it (on a TPU its
    kernels), against this file's float32 forms at the configuration's
    sizes, the root mean square of got - want over that of want, a value:

    * a whole latent-attention layer at the configuration's head widths
      (256 | 256 published) on one sequence of 1,024 rows (the flash
      kernels' whole blocks), from its input norm to W_o: the output and
      the gradient of a seeded weighted sum of it by the rows, W_qa, W_qb,
      W_kva, W_kvb and W_o (`mla_*`);
    * the prediction module end to end on 2,048 rows, from the last
      block's output h and the next tokens' table rows through its two
      norms, W_eh, its whole block (latent attention and the held experts,
      these routed by the bias alone) and its final norm to the step's two
      losses over one head (`joint_loss`: a seeded stand-in for the stack's
      rows, the module's own against the tokens two on with the last
      position masked): the rows out, the loss, and the gradient of a
      seeded weighted sum of both by h, the table rows, the head, W_eh and
      the three norms (`mtp_*`);
    * the held share of an expert layer with its shared expert on 2,048
      seeded rows under THIS file's routing, which the program's own
      router has to arrive at (a row whose k-th and next biased score lie
      within 1e-4, a hundred roundings, is made a zero row first: every
      score a half, the bias alone picks): the output and the gradient of
      a seeded weighted sum by the rows, the router, both expert tensors
      and both shared matrices (`moe_*`); the same seven where every
      assignment goes to a held expert, which takes several passes of the
      layer's buffers (`moe_all_held_*`); and the output alone where none
      does, the shared expert's (`moe_none_held`);
    * the expert layer's output at the cell's `long` tokens
      (`moe_out_long`).

    With `low`, what is compared is this file's forms themselves with
    every input and value in bfloat16: the second reading KERNEL_LIMIT
    lies under. The float32 side is made once a seed (`_cases`). `groups`
    names the values wanted, by their prefix: all of GROUPS, or under a
    planted fault the one group that fault moves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder
    from ray_tpu.models import glm4_moe_lite as program

    groups = groups or tuple(_PLANTED_GROUP) or GROUPS
    case = _cases(cfg, seed, long)
    planted_cfg = _as_planted(cfg)
    bf16, dec = jnp.bfloat16, planted_cfg.decoder(module=True)
    layer_sizes = dict(experts_per_token=cfg.experts_per_token,
                       first=cfg.held[0], routed_scale=cfg.routed_scale,
                       weight_eps=_WEIGHT_EPS, gated=True)

    def mla_program(x, *matrices):
        return (decoder.latent_attention(x, case["as_layer"](*matrices),
                                         dec)[0],)

    def mtp_program(h, emb, head, w_eh, enorm, hnorm, norm, block, x_main,
                    targets):
        module = case["as_module"](w_eh, enorm, hnorm, norm, block)
        run_block = decoder._block_of(
            dec, *decoder._block_keys(dec, [block] * len(dec.kinds))[-1])
        out, _ = decoder.prediction_module(h, emb, module, run_block,
                                           dec.norm_eps)
        return out, program.joint_loss(x_main, out, head, targets,
                                       planted_cfg.mtp_loss_weight)[0]

    def moe_program(x, router, gate_up, down, shared_gate_up, shared_down,
                    bias):
        return (decoder.held_moe_layer(x, router, bias, gate_up, down,
                                       shared_gate_up, shared_down,
                                       **layer_sizes)[0],)

    errors = {}
    if "mla" in groups:
        if low:
            with jax.default_matmul_precision("highest"):
                got = _all_of(case["mla_plain"](bf16), 6)(case["mla_w"],
                                                          *case["mla_exact"])
        else:
            got = _all_of(mla_program, 6)(case["mla_w"], *case["mla_in"])
        errors.update(zip(("mla_out", "mla_dx", "mla_dw_qa", "mla_dw_qb",
                           "mla_dw_kva", "mla_dw_kvb", "mla_dwo"),
                          _rel(got, case["mla_want"])))
    if "mtp" in groups:
        if low:
            with jax.default_matmul_precision("highest"):
                got = _all_of(case["mtp_plain"](bf16), 7)(
                    case["mtp_w"], *case["mtp_exact"], *case["mtp_given"])
        else:
            got = _all_of(mtp_program, 7)(case["mtp_w"], *case["mtp_in"],
                                          *case["mtp_given"])
        errors.update(zip(("mtp_out", "mtp_loss", "mtp_dh", "mtp_dembedded",
                           "mtp_dhead", "mtp_dw_eh", "mtp_denorm",
                           "mtp_dhnorm", "mtp_dnorm"),
                          _rel(got, case["mtp_want"])))
    if "moe" not in groups:
        return errors
    names = ("out", "dx", "drouter", "dgate_up", "ddown", "dshared_gate_up",
             "dshared_down")
    plain = case["moe_plain"](bf16)
    for prefix, (given, want) in case["with_gradients"].items():
        if low:
            with jax.default_matmul_precision("highest"):
                got = _all_of(plain, 6)(*given)
        else:
            got = _all_of(moe_program, 6)(case["moe_w"], *case["moe_in"],
                                          case["biases"][prefix])
        errors.update(zip((f"moe_{prefix}{name}" for name in names),
                          _rel(got, want)))
    for name, (x, b_, given, want) in case["forward"].items():
        if low:
            with jax.default_matmul_precision("highest"):
                got = jax.jit(plain)(*given)
        else:
            got = jax.jit(moe_program)(x, *case["weights"], b_)
        errors["moe_" + name] = _rel(got, want)[0]
    return errors
