"""OLMoE family (ray_tpu.models.moe): config builder, operation and byte
counts, and a plain float32 reference of OLMoE-1B-7B's layer equations
(allenai/OLMoE-1B-7B-0125-Instruct config.json; HF modeling_olmoe.py;
loss weights from the OLMoE paper, arXiv:2409.02060).

The equations, for a layer with input x [T, d] (d 2048, 16 heads of 128,
64 experts of width 1024, 8 a token at the published sizes):

    y  = rmsnorm(x; w_ln1, eps)
    q  = rmsnorm(y Wq; w_qn)    k = rmsnorm(y Wk; w_kn)    v = y Wv
         (q/k norm over all d columns, before the head split)
    q, k -> [B, H, S, hd], rotary (half-split, theta);
    a  = causal softmax(q k^T / sqrt(hd)) v;      h = x + a Wo
    y2 = rmsnorm(h; w_ln2)
    p  = softmax_E(y2 Wr);  (w_1..k, e_1..k) = top-k of p;
         the weights are NOT renormalised (norm_topk_prob false)
    out = h + sum_j w_j (silu(y2 G[e_j]) * (y2 U[e_j])) D[e_j]
    logits = rmsnorm(x_L; w_f) H                   (H untied)
    loss = CE + aux_loss_weight * L_balance + z_loss_weight * L_z
    L_balance = E * sum_e f_e P_e    f_e = assignments to e / rows,
                P_e = mean of p[:, e], rows = all layers' tokens together
                (HF load_balancing_loss_func: sum_e f_e = k, so a
                balanced router gives L_balance = k)
    L_z = mean over those rows of logsumexp(y2 Wr)^2

The reference computes EVERY expert for every token, expert by expert,
and weights each by the top-k mask: no sort, no grouped matmul, no
kernel, and no code shared with ray_tpu. It reads the program's parameter
tree (`wqkv` is Wq|Wk|Wv side by side). The count functions take the
program's config object or the configuration file's dict and import no
jax: per-layer readers call them in run.py's parent process, which must
never initialise a backend."""

from __future__ import annotations

import math

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three and ops/grouped_matmul.py's two (forward and
# the gradient by the rows share one; the gradient by the experts' weights
# is the other).
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_gmm_kernel", "_tgmm_kernel")


def build(config: dict, **overrides):
    """The program's MoEConfig at the file's sizes."""
    from ray_tpu.models import MoEConfig

    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("models/moe.py has multi-head attention only")
    a = config["assumed"]
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              n_layers=config["num_hidden_layers"],
              n_experts=config["num_experts"],
              experts_per_token=config["num_experts_per_tok"],
              d_expert=config["intermediate_size"],
              qk_norm=True,
              tie_embeddings=config["tie_word_embeddings"],
              norm_topk_prob=config["norm_topk_prob"],
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["rms_norm_eps"],
              aux_loss_weight=a["router_aux_loss_coef"],
              z_loss_weight=a["router_z_loss_coef"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    return MoEConfig(**kw)


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test."""
    from ray_tpu.models import make_moe_train_step, moe_init, moe_loss

    init_state, step = make_moe_train_step(cfg, mesh=mesh, rules=rules)
    return (lambda key: moe_init(key, cfg), init_state, step,
            lambda params, batch: moe_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's MoEConfig or the configuration's dict."""
    if isinstance(cfg, dict):
        return dict(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                    e=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                    f=cfg["intermediate_size"], v=cfg["vocab_size"])
    return dict(d=cfg.d_model, layers=cfg.n_layers, e=cfg.n_experts,
                k=cfg.experts_per_token, f=cfg.d_expert, v=cfg.vocab_size)


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul operations one token needs in the forward pass at context
    `seq`: q/k/v/o, the router, its k active SwiGLU experts (three
    matmuls each), causal attention (QK^T and PV over half the square) in
    every layer, and the untied head."""
    s = _dims(cfg)
    d = s["d"]
    per_layer = (2 * d * 3 * d + 2 * d * d + 2 * d * s["e"]
                 + s["k"] * 3 * 2 * d * s["f"])
    attention = 2 * 2 * seq * d / 2
    return s["layers"] * (per_layer + attention) + 2 * d * s["v"]


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the flash backward's second QK^T) is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step,
    all layers: forward 2 matmuls, backward 4 (dV, dP, dQ, dK), each
    2*B*H*S*S*D, halved for the causal mask."""
    s = _dims(cfg)
    bhssd = batch * seq * seq * s["d"]          # heads x head_dim = d
    return s["layers"] * (2 + 4) * 2 * bhssd / 2


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv. bf16."""
    s = _dims(cfg)
    return s["layers"] * (4 + 8) * batch * seq * s["d"] * 2


def expert_matmul_flops(cfg, tokens: int) -> float:
    """Required operations of the grouped matmuls in one train step, all
    layers: every token's k assignments are rows of three grouped matmuls
    forward (gate, up, down) and six backward (each one's gradient by its
    rows and by its weights), 2 * rows * d * f each. The recomputed
    forward (remat) is not counted."""
    s = _dims(cfg)
    rows = tokens * s["k"]
    return s["layers"] * (3 + 6) * 2.0 * rows * s["d"] * s["f"]


def expert_matmul_bytes(cfg, tokens: int) -> float:
    """Least HBM traffic of those nine grouped matmuls a layer: each
    touches its rows [rows, d], its experts' tensor [E, d, f] and its
    other rows [rows, f] once (two read, one written, whichever the
    output is). bf16."""
    s = _dims(cfg)
    rows = tokens * s["k"]
    one = rows * (s["d"] + s["f"]) + s["e"] * s["d"] * s["f"]
    return s["layers"] * (3 + 6) * 2.0 * one


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rotary(x, base):
    """x [b, h, s, hd]; position p rotates the pair (x[i], x[i+hd/2]) by
    p / base**(2i/hd)."""
    import jax.numpy as jnp
    s, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(y, lay, cfg):
    """y [T, d] -> (sum over the top-k experts [T, d], router logits
    [T, E], chosen experts [T, k]). Every expert runs on every token."""
    import jax
    import jax.numpy as jnp

    logits = y @ lay["router"]
    p = jax.nn.softmax(logits, -1)
    w, chosen = jax.lax.top_k(p, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    # [T, E]: an expert's weight where it is among the k, else 0.
    weight = jnp.sum(
        jax.nn.one_hot(chosen, cfg.n_experts, dtype=y.dtype) * w[..., None], 1)

    def one_expert(acc, xs):
        gate, up, down, w_e = xs
        out = (jax.nn.silu(y @ gate) * (y @ up)) @ down
        return acc + w_e[:, None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (lay["expert_gate"], lay["expert_up"], lay["expert_down"], weight.T))
    return out, logits, chosen


def _forward(params, tokens, cfg, dtype=None):
    """(logits [b, s, V], router logits [L*T, E], chosen [L*T, k]), every
    parameter and so every value in `dtype` (float32 unless given)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(dtype or jnp.float32), params)
    x = p["embed"][tokens]
    b, s, d = x.shape
    h, hd, eps = cfg.n_heads, d // cfg.n_heads, cfg.norm_eps
    causal = jnp.tril(jnp.ones((s, s), bool))
    router_logits, chosen = [], []
    for lay in p["layers"]:
        y = _rms_norm(x, lay["ln1"], eps)
        q, k, v = jnp.split(y @ lay["wqkv"], 3, axis=-1)
        if "q_norm" in lay:
            q = _rms_norm(q, lay["q_norm"], eps)
            k = _rms_norm(k, lay["k_norm"], eps)
        q, k, v = (t.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        q, k = _rotary(q, cfg.rope_theta), _rotary(k, cfg.rope_theta)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
        x = x + a.transpose(0, 2, 1, 3).reshape(b, s, d) @ lay["wo"]
        y = _rms_norm(x, lay["ln2"], eps).reshape(b * s, d)
        out, lg, ch = _experts(y, lay, cfg)
        x = x + out.reshape(b, s, d)
        router_logits.append(lg)
        chosen.append(ch)
    x = _rms_norm(x, p["lnf"], eps)
    head = p["head"] if "head" in p else p["embed"].T
    return x @ head, jnp.concatenate(router_logits), jnp.concatenate(chosen)


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    return _forward(params, tokens, cfg)[0]


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """Mean next-token cross entropy plus the two weighted router losses,
    in float32. `dtype` is for setting the comparison's limit only: the
    same reference with every parameter and value in a lower precision
    (bfloat16) has to come out as not correct (PERF.md)."""
    import jax
    import jax.numpy as jnp

    logits, router_logits, chosen = _forward(params, tokens, cfg, dtype)
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
    # HF load_balancing_loss_func over the concatenated layers' rows.
    e = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(chosen, e), 0)            # [k, E]
    prob = jnp.mean(jax.nn.softmax(router_logits, -1), 0)  # [E]
    balance = e * jnp.sum(f * prob[None, :])
    z = jnp.mean(jax.nn.logsumexp(router_logits, -1) ** 2)
    return ce + cfg.aux_loss_weight * balance + cfg.z_loss_weight * z
