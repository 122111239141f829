"""LFM2-MoE family (ray_tpu.models.lfm2_moe): config builder, operation and
byte counts, and a plain float32 reference of LFM2-8B-A1B's layer
equations (LiquidAI/LFM2-8B-A1B config.json, model_type lfm2_moe; the
public modeling_lfm2_moe.py, whose router is DeepSeek-V3's,
arXiv:2412.19437).

The equations (d 2048, eps 1e-5, no bias anywhere; T tokens):

    x_0 = E[tokens]
    layer l:  h = x + mixer_l(rmsnorm(x; w_op));  x' = h + ffn_l(rmsnorm(h;
              w_ffn))
    conv       [B | C | x] = y W_in                   W_in [2048, 6144]
               u = B * x
               c_t = sum_{j=0..2} w[j] * u_{t-2+j}    per channel, causal,
                     zeros before a sequence's first token, nothing read
                     across the sequences of a batch; w [3, 2048]
               out = (C * c) W_out
    full_attention
               q = y W_q -> [32, 64]; k | v = y W_kv -> [8, 64] each;
               q, k <- rmsnorm over each head's 64 columns (one [64]
               weight each), then rotary over all 64 columns at base 1e6
               in HF's rotate_half form: the pair (t[i], t[i + 32]) turns
               by p / 1e6^(2i / 64); query head h reads kv head h // 4;
               out = causal softmax(q k^T / 8) v W_o
    dense (l < num_dense_layers)
               W2 (silu(W1 y) * W3 y), width 7168
    experts    s = sigmoid(y W_r) in R^32, float32; the 4 experts of a
               token are the top 4 of s + b (b the selection bias: it
               picks and never weighs; in the loss, a training step's, b
               has first moved `bias_rounds` rounds of its rule on the
               batch's own s: `_bias_moved`); w_j = s[e_j] / (sum_j s[e_j]
               + 1e-6), times routed_scaling_factor 1;
               out = sum over the HELD e_j of w_j W2[e_j] (silu(W1[e_j] y)
                     * W3[e_j] y), width 1792; no shared expert
    logits = rmsnorm(x_L; w_f) E^T                     (tied)
    loss   = cross entropy

One chip's share: the file's `num_experts` experts from the first on are
held; what the absent ones would add is left out, here as in the program,
and the partial result goes on to the next layer. The vocabulary is the
file's slice.

The reference runs the convolution as three shifted products a token,
attention as a plain masked softmax, EVERY held expert for every token
masked by the reference's own routing: no sort, no grouped matmul, no
kernel, no cache, and no code shared with ray_tpu. It reads the program's
parameter tree (`conv_in` is W_in, `conv_taps` w, `wkv` W_k | W_v side by
side, `w_gate` / `w_up` / `w_down` W1 / W3 / W2, `expert_gate_up` an
expert's W1 | W3 side by side, `router_bias` b). At long sequences it
works in blocks so that it fits beside the program's parameters. The
count functions take the program's config object or the configuration
file's dict and import no jax: per-layer readers call them in run.py's
parent process, which must never initialise a backend."""

from __future__ import annotations

import contextlib
import importlib.machinery
import importlib.util
import math

# A tree from before the family says so as the cell is looked up, in
# run.py's own process, before a cluster or a chip is touched
# (families/granite_hybrid.py has why it is looked for this way).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.lfm2_moe", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models.lfm2_moe: "
                      "it cannot run a lfm2-moe configuration")

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three, ops/grouped_matmul.py's two (three scopes),
# ops/short_conv.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_gmm_kernel", "_tgmm_kernel",
                  "_conv_fwd_kernel", "_conv_bwd_kernel")

_QUERY_BLOCK = 1024
_LOSS_ROWS = 2048
CONV, FULL = "conv", "full_attention"


def build(config: dict, **overrides):
    """The program's Lfm2MoeConfig at the file's sizes."""
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    for key, want in (("conv_bias", False), ("norm_topk_prob", True),
                      ("use_expert_bias", True)):
        if config[key] != want:
            raise ValueError(f"models/lfm2_moe.py has {key} = {want!r} "
                             f"only, not {config[key]!r}")
    a = config["assumed"]
    if not a["tie_word_embeddings"]:
        raise ValueError("models/lfm2_moe.py ties the head to the table")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              n_kv_heads=config["num_key_value_heads"],
              head_dim=a["head_dim"],
              layer_types=tuple(config["layer_types"]),
              n_dense_layers=config["num_dense_layers"],
              conv_taps=config["conv_L_cache"],
              d_ff=config["intermediate_size"],
              n_experts=config["deployment_sizes"]["num_experts"],
              experts_held=(config["deployment_sizes"]["first_expert_held"],
                            config["num_experts"]),
              experts_per_token=config["num_experts_per_tok"],
              d_expert=config["moe_intermediate_size"],
              routed_scale=float(config["routed_scaling_factor"]),
              topk_weight_eps=a["topk_weight_eps"],
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["norm_eps"],
              bias_rounds=a["bias_rounds"],
              balance_tokens=a["balance_tokens"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)


# The cell's second limit, on the layers this configuration brought: the
# largest of kernel_errors' relative errors. Two readings on the v5e at
# the published sizes (limit_readings.py on seeds 0, 11 and 2147483900, PR
# 48; PERF.md section 4): the program 0.0063 to 0.0065 (the largest a
# gradient of the expert layer's first matrix over two passes or of the
# norm a head inside attention; the cell's own seed 0 reads 0.0063); this
# file's forms with every input and value in bfloat16, the nearest
# precision below, 0.0106 to 0.0155 (the expert layer's gradient by its
# rows, summed over a token's experts in bfloat16, and the norms' inside
# attention; the convolution, elementwise but for three sums, reads as
# the program's). The limit is their geometric mean, 1.28 times of room
# on either side. Each of the eight structural faults below reads 0.247
# or more on every seed.
KERNEL_LIMIT = 0.0083


def hold_kernels(cfg):
    """Refuse a program whose gated convolution, per-head norm or held
    gated-expert layer is further from this file's float32 forms than
    KERNEL_LIMIT: the loss at initialisation, which drivers/train.py
    compares, hardly sees a layer's structure (PERF.md section 4), so the
    cell holds the layers this configuration brought to a limit of their
    own before it hands the program over."""
    from .. import harness

    errors = kernel_errors(cfg)
    worst = max(errors, key=errors.get)
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program is off the float32 reference by {errors[worst]:.3g} "
        f"of the largest value in {worst} (limit {KERNEL_LIMIT}): {errors}")


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    the layers held to KERNEL_LIMIT first where the kernels are the
    chip's (elsewhere tier-1 holds them to the reference at 1e-4)."""
    import jax

    from ray_tpu.models.lfm2_moe import (lfm2_moe_init, lfm2_moe_loss,
                                         make_lfm2_moe_train_step)

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = make_lfm2_moe_train_step(cfg, mesh=mesh, rules=rules)
    return (lambda key: lfm2_moe_init(key, cfg), init_state, step,
            lambda params, batch: lfm2_moe_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _thirds_changed(change):
    """The convolution handed B | C | x with `change(B, C, x)` for them."""
    def faulty(conv, bcx, weight, tail=None):
        import jax.numpy as jnp
        return conv(jnp.concatenate(change(*jnp.split(bcx, 3, axis=-1)), -1),
                    weight, tail)
    return faulty


def _a_row_ahead(t):
    import jax.numpy as jnp
    return jnp.concatenate([t[:, 1:], jnp.zeros_like(t[:, :1])], axis=1)


def _read_across_sequences(conv, bcx, weight, tail=None):
    """The batch's sequences convolved as ONE: the second's first rows
    read the first's last."""
    b, s, width = bcx.shape
    y, new_tail = conv(bcx.reshape(1, b * s, width), weight, tail)
    return y.reshape(b, s, -1), new_tail


def _silu_on_the_wrong_half(layer, x, router_w, router_bias, w_up, *rest,
                            **sizes):
    """up | gate where the layer holds gate | up: silu lands on W3 y."""
    import jax.numpy as jnp
    gate, up = jnp.split(w_up, 2, axis=-1)
    return layer(x, router_w, router_bias, jnp.concatenate([up, gate], -1),
                 *rest, **sizes)


def _norm_over_all_columns(norm, t, weight, eps):
    """ONE RMSNorm over all of q's (or k's) columns, not one a head of
    64."""
    import jax.numpy as jnp
    return norm(t, jnp.tile(weight, t.shape[-1] // weight.shape[0]), eps)


def _by_plain_layer(fault: str):
    """The expert layer as this file's plain form in the program's own
    precision, with one line wrong: `bias_added_to_the_weights` (w_j from
    s + b, not s), `absent_rows_computed` (an assignment to an absent
    expert e goes through held expert e mod held)."""
    def faulty(layer, x, router_w, router_bias, w_up, w_down, *shared,
               experts_per_token, first, routed_scale, weight_eps,
               bias_rounds=0, gated=True):
        out = _plain_experts(
            x, router_w, router_bias, w_up, w_down, k=experts_per_token,
            first=first, scale=routed_scale, eps=weight_eps, fault=fault,
            rounds=bias_rounds)[0]
        real = layer(x, router_w, router_bias, w_up, w_down, *shared,
                     experts_per_token=experts_per_token, first=first,
                     routed_scale=routed_scale, weight_eps=weight_eps,
                     bias_rounds=bias_rounds, gated=gated)[1]
        return out.astype(x.dtype), real
    return faulty


# What limit_readings.py plants in the program, one at a time, each a
# fault of structure in what this configuration brought: (the name on
# ray_tpu.models.decoder that stands for the faulty one meanwhile, the
# faulty one given the real one first).
STRUCTURAL_FAULTS = {
    "tap_read_one_row_ahead": ("gated_short_conv", _thirds_changed(
        lambda B, C, x: (_a_row_ahead(B), C, _a_row_ahead(x)))),
    "rows_read_across_sequences": ("gated_short_conv",
                                   _read_across_sequences),
    "b_gate_dropped": ("gated_short_conv", _thirds_changed(
        lambda B, C, x: (B * 0 + 1, C, x))),
    "c_gate_dropped": ("gated_short_conv", _thirds_changed(
        lambda B, C, x: (B, C * 0 + 1, x))),
    "silu_on_the_wrong_half": ("held_moe_layer", _silu_on_the_wrong_half),
    "absent_rows_computed": (
        "held_moe_layer", _by_plain_layer("absent_rows_computed")),
    "bias_added_to_the_weights": (
        "held_moe_layer", _by_plain_layer("bias_added_to_the_weights")),
    "norm_over_all_columns": ("head_rms_norm", _norm_over_all_columns),
}
PRECISION_FAULTS = {}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault` in every layer of its kind: models.decoder
    calls the convolution, the per-head norm and the expert layer through
    its own names, one of which stands for the faulty one meanwhile. Trace
    the program inside; a function jitted before keeps what it traced."""
    import functools

    from ray_tpu.models import decoder

    name, faulty = STRUCTURAL_FAULTS[fault]
    real = getattr(decoder, name)
    setattr(decoder, name, functools.partial(faulty, real))
    try:
        yield
    finally:
        setattr(decoder, name, real)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's Lfm2MoeConfig or the configuration's dict.
    `held` experts of `e` the router spans."""
    if isinstance(cfg, dict):
        kinds, dense = cfg["layer_types"], cfg["num_dense_layers"]
        s = dict(d=cfg["hidden_size"], v=cfg["vocab_size"],
                 h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
                 hd=cfg["assumed"]["head_dim"], K=cfg["conv_L_cache"],
                 ff=cfg["intermediate_size"],
                 e=cfg["deployment_sizes"]["num_experts"],
                 held=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                 f=cfg["moe_intermediate_size"])
    else:
        kinds, dense = cfg.layer_types, cfg.n_dense_layers
        s = dict(d=cfg.d_model, v=cfg.vocab_size, h=cfg.n_heads,
                 kv=cfg.n_kv_heads, hd=cfg.head_dim, K=cfg.conv_taps,
                 ff=cfg.d_ff, e=cfg.n_experts, held=cfg.held[1],
                 k=cfg.experts_per_token, f=cfg.d_expert)
    s.update(conv_layers=list(kinds).count(CONV),
             attention_layers=list(kinds).count(FULL),
             dense_layers=dense, expert_layers=len(kinds) - dense)
    return s


def _held_rows(s: dict, tokens: int) -> float:
    """Rows a layer's held experts see under a balanced router: every
    token's k assignments fall evenly on the e experts."""
    return tokens * s["k"] * s["held"] / s["e"]


def held_rows_balanced(cfg, tokens: int) -> float:
    """The rows a layer's held experts see a step of `tokens` under a
    balanced router: what the counts below take the routed work to be, and
    what the step's `expert_rows_held` is read against
    (chipbench/step_counters.py)."""
    return _held_rows(_dims(cfg), tokens)


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul and convolution operations one token needs in the forward
    pass at context `seq`. A convolution mixer: the input and output
    projections, the taps and the two gates. Attention: q, k | v, o and
    causal attention (QK^T and PV over half the square). A dense layer's
    three matrices. An expert layer: the router over all e outputs and the
    BALANCED share of the routed work (k held / e assignments a token,
    three matmuls each); nothing made again. The tied head once."""
    s = _dims(cfg)
    d, q_d = s["d"], s["h"] * s["hd"]
    conv = 2 * d * 3 * d + 2 * d * d + (2 * s["K"] + 2) * d
    attention = (2 * d * q_d + 2 * d * 2 * s["kv"] * s["hd"] + 2 * q_d * d
                 + 2 * 2 * seq * q_d / 2)
    dense = 3 * 2 * d * s["ff"]
    experts = 2 * d * s["e"] + _held_rows(s, 1) * 3 * 2 * d * s["f"]
    return (s["conv_layers"] * conv + s["attention_layers"] * attention
            + s["dense_layers"] * dense + s["expert_layers"] * experts
            + 2 * d * s["v"])


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the kernels' tiles made again in their backward) is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step,
    the attention layers only: forward 2 matmuls, backward 4, each
    2*B*H*S*S*D, halved for the causal mask."""
    s = _dims(cfg)
    return (s["attention_layers"] * (2 + 4) * 2 * batch * seq * seq
            * s["h"] * s["hd"] / 2)


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv, k and v
    counted at their 8 heads, not their copies across a group. bf16."""
    s = _dims(cfg)
    q = batch * seq * s["h"] * s["hd"] * 2
    kv = batch * seq * s["kv"] * s["hd"] * 2
    return s["attention_layers"] * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def expert_matmul_flops(cfg, tokens: int) -> float:
    """Required operations of the grouped matmuls in one train step, the
    expert layers only, for a BALANCED router: the held experts' rows
    (tokens x k x held / e a layer) go through three matmuls forward
    (gate, up, down; gate and up are one grouped matmul of twice the
    width) and six backward (each one's gradient by its rows and by its
    weights), 2 * rows * d * f each. What remat makes again is not
    counted."""
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


def short_conv_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the convolution kernels in one train step,
    the convolution layers only, a channel and token: forward B * x, K
    multiply-adds and C * c (2K + 2); backward g = C * dy, dy * c with c's
    K multiply-adds, du's K, du * x, du * B and the taps' K (6K + 4)."""
    s = _dims(cfg)
    return s["conv_layers"] * batch * seq * s["d"] * (8.0 * s["K"] + 6)


def short_conv_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads B, C, x and
    writes y (4 values a channel and token); backward reads B, C, x, dy
    and writes dB, dC, dx (7). bf16. The bytes bound applies: at d 2048
    and 32,768 tokens 1.48 GB a layer, 1.8 ms at 819 GB/s, against 2.0
    GFLOP."""
    s = _dims(cfg)
    return s["conv_layers"] * batch * seq * s["d"] * (4 + 7) * 2.0


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    import jax.numpy as jnp
    return 1.0 / (1.0 + jnp.exp(-x))


def _blocks(n: int, limit: int) -> int:
    """The largest block size up to `limit` that divides n."""
    return max(b for b in range(1, min(n, limit) + 1) if n % b == 0)


def _rotate_half(t, base: float):
    """HF's rotary embedding of t [b, s, ..., hd] at positions 0..s-1:
    t * cos + rotate_half(t) * sin with rotate_half(t) = (-t2 | t1) and
    the angles p / base^(2i / hd) repeated over both halves."""
    import jax.numpy as jnp
    s, hd = t.shape[1], t.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], -1).reshape(
        (1, s) + (1,) * (t.ndim - 3) + (hd,))
    t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
    return (t * jnp.cos(angles).astype(t.dtype)
            + jnp.concatenate([-t2, t1], -1) * jnp.sin(angles).astype(t.dtype))


def head_norm(t, weight, eps: float):
    """An RMSNorm over the last axis, a head's columns: t [..., hd]."""
    return _rms_norm(t, weight, eps)


def _attention(y, lay, cfg):
    """y [b, s, d] -> [b, s, d]: a norm a head on q and k, rotary, scores
    over sqrt(head_dim), each kv head serving its group of query heads;
    query blocks against all keys."""
    import jax
    import jax.numpy as jnp

    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (y @ lay["wq"]).reshape(b, s, kvh, h // kvh, hd)
    k, v = jnp.split(y @ lay["wkv"], 2, axis=-1)
    k, v = k.reshape(b, s, kvh, hd), v.reshape(b, s, kvh, hd)
    q = _rotate_half(head_norm(q, lay["q_head_norm"], cfg.norm_eps),
                     cfg.rope_theta)
    k = _rotate_half(head_norm(k, lay["k_head_norm"], cfg.norm_eps),
                     cfg.rope_theta)
    block = _blocks(s, _QUERY_BLOCK)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, first = args                       # [b, block, kvh, group, hd]
        sc = jnp.einsum("bqjgd,bkjd->bjgqk", qb, k) / math.sqrt(hd)
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("bjgqk,bkjd->bqjgd", p.astype(v.dtype), v)

    out = jax.lax.map(one_block, (
        q.reshape(b, s // block, block, kvh, h // kvh, hd).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, h * hd) @ lay["wo"]


def gated_conv(bcx, taps):
    """C * conv(B * x) of bcx = B | C | x [b, s, 3d] under taps [K, d]:
    K shifted products a token, zeros before each sequence's first."""
    import jax.numpy as jnp

    s, K = bcx.shape[1], taps.shape[0]
    B, C, x = jnp.split(bcx, 3, axis=-1)
    padded = jnp.pad(B * x, ((0, 0), (K - 1, 0), (0, 0)))
    return C * sum(padded[:, j:j + s] * taps[j] for j in range(K))


def _short_conv(y, lay, cfg):
    return gated_conv(y @ lay["conv_in"], lay["conv_taps"]) @ lay["conv_out"]


def _dense(y, lay):
    return (_silu(y @ lay["w_gate"]) * (y @ lay["w_up"])) @ lay["w_down"]


def _bias_moved(scores, bias, k: int, rounds: int):
    """b after `rounds` rounds of b <- b + r sign(mean(c) - c(b)) on
    `scores` [T, E], c(b) the tokens whose top k of scores + b hold each
    expert, r falling geometrically from the scores' spread to 1e-4. The
    tokens are ranked anew every eighth round; between, an expert's count
    is of the tokens where it clears its bar, the k-th best of the other
    experts' biased scores as the last ranking left them. In float32; the
    result with its mean taken off."""
    import jax.numpy as jnp

    scores, bias = scores.astype(jnp.float32), bias.astype(jnp.float32)
    tokens, e = scores.shape
    spread = jnp.maximum(jnp.max(scores) - jnp.min(scores), 1e-4)
    steps = spread * (1e-4 / spread) ** jnp.linspace(0.0, 1.0, rounds)
    for first in range(0, rounds, 8):
        biased = scores + bias
        ranked = -jnp.sort(-biased, axis=-1)
        last_in, first_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
        over_bar = biased - jnp.where(biased >= last_in, first_out, last_in)
        moved = jnp.zeros_like(bias)
        for r in steps[first:first + 8]:
            count = jnp.sum(over_bar + moved > 0, axis=0).astype(jnp.float32)
            moved = moved + r * jnp.sign(tokens * k / e - count)
        bias = bias + moved
    return bias - jnp.mean(bias)    # the same choices, the mean at zero


def _plain_experts(y, router, bias, gate_up, down, *, k: int, first: int,
                   scale: float, eps: float, chosen=None, fault=None,
                   rounds: int = 0):
    """y [T, d] -> (the held experts' part [T, d], the chosen experts
    [T, k], the scores [T, E]). Every held expert runs on every token and
    is weighted by the routing's mask; `chosen` given, the routing is that
    one and not the reference's own; with `rounds` the bias moves that
    many rounds on these scores first."""
    import jax
    import jax.numpy as jnp

    scores = _sigmoid(y.astype(router.dtype) @ router)
    if rounds:
        bias = jax.lax.stop_gradient(
            _bias_moved(scores, bias, k, rounds)).astype(scores.dtype)
    picked_by = scores + bias
    if chosen is None:
        chosen = jax.lax.top_k(picked_by, k)[1]
    w = jnp.take_along_axis(
        picked_by if fault == "bias_added_to_the_weights" else scores,
        chosen, -1)
    w = scale * w / (jnp.sum(w, -1, keepdims=True) + eps)
    held = gate_up.shape[0]
    local = chosen - first
    if fault == "absent_rows_computed":
        local = local % held
    # [T, held]: a held expert's weight where it is among the k, else 0.
    weight = jnp.sum(
        jax.nn.one_hot(local, held, dtype=w.dtype) * w[..., None], 1)

    def one_expert(acc, xs):
        gu, dn, w_e = xs
        w1, w3 = jnp.split(gu, 2, axis=-1)
        out = (_silu(y @ w1) * (y @ w3)) @ dn
        return acc + w_e[:, None].astype(acc.dtype) * out, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          (gate_up, down, weight.T))
    return out, chosen, scores


def _experts(y, lay, cfg, rounds=0):
    b, s, d = y.shape
    out = _plain_experts(
        y.reshape(b * s, d), lay["router"], lay["router_bias"],
        lay["expert_gate_up"], lay["expert_down"], k=cfg.experts_per_token,
        first=cfg.held[0], scale=cfg.routed_scale, eps=cfg.topk_weight_eps,
        rounds=rounds)[0]
    return out.reshape(b, s, d)


def _hidden(params, tokens, cfg, dtype=None, rounds=0):
    """(final-norm rows [b, s, d], the head [d, V]), every parameter and
    so every value in `dtype` (float32 unless given); `rounds` of each
    selection bias's rule before its layer routes (a training step's
    forward)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    x = p["embed"][tokens]
    eps = cfg.norm_eps
    for i, (kind, lay) in enumerate(zip(cfg.layer_types, p["layers"])):
        y = _rms_norm(x, lay["ln1"], eps)
        mixed = _short_conv(y, lay, cfg) if kind == CONV \
            else _attention(y, lay, cfg)
        x = (x + mixed).astype(p["embed"].dtype)
        y = _rms_norm(x, lay["ln2"], eps)
        out = _dense(y, lay) if i < cfg.n_dense_layers \
            else _experts(y, lay, cfg, rounds)
        x = (x + out).astype(p["embed"].dtype)
    return _rms_norm(x, p["lnf"], eps), p["embed"].T


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
def kernel_errors(cfg, seed: int = 0, low: bool = False,
                  long: int = 32768) -> dict:
    """What the program runs as models.decoder calls it (on a TPU its
    kernels), against this file's float32 forms at the configuration's
    sizes, the largest |got - want| over the largest |want| of each value:

    * the gated convolution on a batch of two sequences of 1,040 rows
      (two row blocks and a ragged third): y and the gradients of a seeded
      weighted sum of it by B, C, x and the taps (`conv_*`); the second
      sequence's first rows are where a row read across the boundary
      would show;
    * the norm a head of 64 columns over q's width (`head_norm`), and
      where it sits: an attention layer's output on one sequence of 1,024
      rows (the norm a head on q and k, rotary, the softmax, the output
      projection) and the gradient of a seeded weighted sum of it by the
      rows and both norms' weights (`attn_*`);
    * the held share of an expert layer on 2,048 seeded rows under THIS
      file's routing, which the program's own router has to arrive at (a
      row whose k-th and next biased score lie within 1e-4, a hundred
      roundings, is made a zero row first: every score a half, the bias
      alone picks): the output and the gradient of a seeded weighted sum
      by the rows, the router and both expert tensors (`moe_*`); the same
      five where every assignment goes to a held expert, which takes two
      passes of the layer's buffers (`moe_all_held_*`); and the output
      alone where none does, no pass at all (`moe_none_held`);
    * both at the cell's `long` tokens, forward only: the convolution's y
      as four sequences (`conv_y_long`) and the expert layer's output
      (`moe_out_long`).

    With `low`, what is compared is this file's forms themselves with
    every input and value in bfloat16: the second reading KERNEL_LIMIT
    lies under."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    f32, bf16 = jnp.float32, jnp.bfloat16
    d, E, k, K = (cfg.d_model, cfg.n_experts, cfg.experts_per_token,
                  cfg.conv_taps)
    first, held = cfg.held
    b, L, T = 2, 1040, 2048
    normal = jax.random.normal
    key = jax.random.PRNGKey(seed)

    def rel(got, want):
        return [float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
                      / jnp.max(jnp.abs(w.astype(f32))))
                for g, w in zip(got, want)]

    def all_of(fn, n):
        """The function's outputs and the gradients of a seeded weighted
        sum of them by its first `n` arguments, one program."""
        def run(weights, *given):
            def scalar(*diff):
                outs = fn(*diff, *given[n:])
                return sum(jnp.sum(o.astype(f32) * w)
                           for o, w in zip(outs, weights)), outs
            (_, outs), grads = jax.value_and_grad(
                scalar, argnums=tuple(range(n)), has_aux=True)(*given[:n])
            return (*outs, *grads)
        return jax.jit(run)

    errors = {}
    # -- the gated convolution ----------------------------------------------
    kc = jax.random.split(jax.random.fold_in(key, 1), 4)
    bound = K ** -0.5

    def conv_inputs(kk, batch, length):
        return (normal(kk, (batch, length, 3 * d)).astype(cfg.dtype),
                jax.random.uniform(kc[1], (K, d), minval=-bound,
                                   maxval=bound).astype(cfg.dtype))

    def conv_program(bcx, taps):
        return (decoder.gated_short_conv(bcx, taps, None)[0],)

    def conv_plain(dtype):
        return lambda bcx, taps: (gated_conv(bcx.astype(dtype),
                                             taps.astype(dtype)),)

    def thirds(values):
        y, dbcx, dtaps = values
        return (y, *jnp.split(dbcx, 3, axis=-1), dtaps)

    conv_in = conv_inputs(kc[0], b, L)
    conv_w = (normal(kc[2], (b, L, d)),)
    exact = tuple(t.astype(f32) for t in conv_in)
    with jax.default_matmul_precision("highest"):
        want = all_of(conv_plain(f32), 2)(conv_w, *exact)
        got = all_of(conv_plain(bf16), 2)(conv_w, *exact) if low else None
    if not low:
        got = all_of(conv_program, 2)(conv_w, *conv_in)
    errors.update(zip(("conv_y", "conv_dB", "conv_dC", "conv_dx",
                       "conv_dtaps"), rel(thirds(got), thirds(want))))
    seqs = max(1, long // 8192)
    conv_long = conv_inputs(kc[3], seqs, long // seqs)
    want = jax.jit(conv_plain(f32))(*conv_long)
    got = jax.jit(conv_plain(bf16) if low else conv_program)(*conv_long)
    errors["conv_y_long"] = rel(got, want)[0]

    # -- the norm a head, alone and where it sits in attention --------------
    kn = jax.random.split(jax.random.fold_in(key, 2), 8)
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = normal(kn[0], (b, L, h * hd)).astype(cfg.dtype)
    w_n = 1.0 + 0.1 * normal(kn[1], (hd,))
    heads = t.reshape(b, L, -1, hd)
    want = head_norm(heads.astype(f32), w_n, cfg.norm_eps).reshape(t.shape)
    if low:
        got = head_norm(heads.astype(bf16), w_n.astype(bf16),
                        cfg.norm_eps).reshape(t.shape)
    else:
        got = decoder.head_rms_norm(t, w_n, cfg.norm_eps)
    errors["head_norm"] = rel([got], [want])[0]
    rows_a = 1024                       # the flash kernels' whole blocks
    lay = {"wq": normal(kn[2], (d, h * hd)) * d ** -0.5,
           "wkv": normal(kn[3], (d, 2 * kvh * hd)) * d ** -0.5,
           "wo": normal(kn[4], (h * hd, d)) * (h * hd) ** -0.5}
    lay = {name: m.astype(cfg.dtype) for name, m in lay.items()}
    attn_in = (normal(kn[5], (1, rows_a, d)).astype(cfg.dtype), w_n,
               1.0 + 0.1 * normal(kn[6], (hd,)))
    attn_w = (normal(kn[7], (1, rows_a, d)),)
    dec = cfg.decoder()

    def attn_program(y, q_norm, k_norm):
        return (decoder.attention(y, {**lay, "q_head_norm": q_norm,
                                      "k_head_norm": k_norm}, dec)[0],)

    def attn_plain(dtype):
        def fn(y, q_norm, k_norm):
            given = {**lay, "q_head_norm": q_norm, "k_head_norm": k_norm}
            return (_attention(y.astype(dtype), {
                name: m.astype(dtype) for name, m in given.items()}, cfg),)
        return fn

    exact = (attn_in[0].astype(f32), *attn_in[1:])
    with jax.default_matmul_precision("highest"):
        want = all_of(attn_plain(f32), 3)(attn_w, *exact)
        got = all_of(attn_plain(bf16), 3)(attn_w, *exact) if low else None
    if not low:
        got = all_of(attn_program, 3)(attn_w, *attn_in)
    errors.update(zip(("attn_out", "attn_dy", "attn_dq_norm", "attn_dk_norm"),
                      rel(got, want)))

    # -- the held share of an expert layer ----------------------------------
    km = jax.random.split(jax.random.fold_in(key, 3), 7)
    f = cfg.d_expert
    weights = (
        normal(km[1], (d, E)) * d ** -0.5,                         # router
        (normal(km[2], (held, d, 2 * f)) * d ** -0.5).astype(cfg.dtype),
        (normal(km[3], (held, f, d)) * f ** -0.5).astype(cfg.dtype))
    moe_w = (normal(km[4], (T, d)),)
    bias = 0.1 * normal(km[5], (E,))
    sizes = dict(experts_per_token=k, first=first,
                 routed_scale=cfg.routed_scale,
                 weight_eps=cfg.topk_weight_eps, gated=True)

    def own_choice(x, bias):
        """(The k experts this file's router picks for each row, the rows
        whose pick a rounding could turn.)"""
        best, chosen = jax.lax.top_k(
            _sigmoid(x.astype(f32) @ weights[0]) + bias, k + 1)
        return chosen[:, :k], best[:, k - 1] - best[:, k] < 1e-4

    def rows(kk, n):
        x = normal(kk, (n, d)).astype(cfg.dtype)
        return jnp.where(own_choice(x, bias)[1][:, None], 0, x)

    def moe_program(x, router, gate_up, down, bias):
        return (decoder.held_moe_layer(x, router, bias, gate_up, down,
                                       **sizes)[0],)

    def moe_plain(dtype):
        def fn(x, router, gate_up, down, bias, chosen):
            x, router, gate_up, down, bias = (
                t.astype(dtype) for t in (x, router, gate_up, down, bias))
            return (_plain_experts(x, router, bias, gate_up, down, k=k,
                                   first=first, scale=cfg.routed_scale,
                                   eps=cfg.topk_weight_eps,
                                   chosen=chosen)[0],)
        return fn

    # two skewed routings: a bias no score outweighs on the first k held
    # experts (every assignment held: two passes where half the experts
    # are held); on k absent ones (none held: no pass). Where every expert
    # is held the second is the first again.
    absent = [e for e in range(E) if not first <= e < first + held]
    skewed = {
        "all_held": jnp.zeros((E,)).at[first:first + k].set(10.0),
        "none_held": jnp.zeros((E,)).at[
            jnp.array((absent or list(range(k)))[:k])].set(10.0)}
    names = ("out", "dx", "drouter", "dgate_up", "ddown")
    with jax.default_matmul_precision("highest"):
        moe_in = (rows(km[0], T), *weights)
        exact = tuple(t.astype(f32) for t in moe_in)
        plain = moe_plain(bf16)
        want, got = {}, {}
        for name, b_ in (("", bias), ("all_held_", skewed["all_held"])):
            given = (moe_w, *exact, b_, own_choice(moe_in[0], b_)[0])
            want[name] = all_of(moe_plain(f32), 4)(*given)
            if low:
                got[name] = all_of(plain, 4)(*given)
        forward = {"out_long": (rows(km[6], long), bias),
                   "none_held": (moe_in[0], skewed["none_held"])}
        want_forward, got_forward = {}, {}
        for name, (x, b_) in forward.items():
            given = (x.astype(f32), *exact[1:], b_, own_choice(x, b_)[0])
            want_forward[name] = jax.jit(moe_plain(f32))(*given)
            if low:
                got_forward[name] = jax.jit(plain)(*given)
    if not low:
        got = {"": all_of(moe_program, 4)(moe_w, *moe_in, bias),
               "all_held_": all_of(moe_program, 4)(moe_w, *moe_in,
                                                   skewed["all_held"])}
        got_forward = {name: jax.jit(moe_program)(x, *weights, b_)
                       for name, (x, b_) in forward.items()}
    for prefix in want:
        errors.update(zip((f"moe_{prefix}{n}" for n in names),
                          rel(got[prefix], want[prefix])))
    for name in forward:
        # none held: the output is zero, and so must the program's be
        scale = [jnp.ones(())] if name == "none_held" else want_forward[name]
        errors["moe_" + name] = float(
            jnp.max(jnp.abs(got_forward[name][0].astype(f32)
                            - want_forward[name][0]))
            / jnp.max(jnp.abs(scale[0])))
    return errors
