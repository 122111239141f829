"""GPT family (ray_tpu.models.gpt): config builder, operation and byte
counts, and a plain float32 reference of the repo's decoder equations.

The reference follows what ray_tpu's decoder computes — RMSNorm, rotary
positions (half-split), no biases, tanh-GELU, tied output head — which is
GPT-2's matmul shapes and not GPT-2's checkpoint format; the configuration
file lists these departures. It shares no code with ray_tpu.models."""

from __future__ import annotations

import math

# The Pallas kernels a lowered train step of this family must call
# (ops/attention.py); neither interpret mode nor mha_reference has them.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")


def build(config: dict, **overrides):
    """The program's GPTConfig at the file's sizes."""
    from ray_tpu.models import GPTConfig

    a = config.get("assumed", {})
    d = config["n_embd"]
    kw = dict(vocab_size=a.get("padded_vocab_size", config["vocab_size"]),
              d_model=d, n_heads=config["n_head"],
              n_layers=config["n_layer"],
              d_ff=config.get("n_inner") or 4 * d,
              max_seq_len=config["n_positions"],
              tie_embeddings=config.get("tie_word_embeddings", True))
    kw.update(overrides)
    return GPTConfig(**kw)


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test."""
    from ray_tpu.models import gpt_init, gpt_loss, make_train_step

    init_state, step = make_train_step(cfg, mesh=mesh, rules=rules)
    return (lambda key: gpt_init(key, cfg), init_state, step,
            lambda params, batch: gpt_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone
# ---------------------------------------------------------------------------
def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul operations one token needs in the forward pass at context
    `seq`: the four projections and the MLP of every layer, causal
    attention (QK^T and PV over half the square), and the output head at
    the padded vocabulary the program multiplies by."""
    d, f = cfg.d_model, cfg.d_ff
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * f
    attention = 2 * 2 * seq * d / 2
    return cfg.n_layers * (per_layer + attention) + 2 * d * cfg.vocab_size


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the flash backward's second QK^T) is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step,
    all layers: forward 2 matmuls, backward 4 (dV, dP, dQ, dK), each
    2*B*H*S*S*D, halved for the causal mask."""
    bhssd = batch * cfg.n_heads * seq * seq * cfg.head_dim
    return cfg.n_layers * (2 + 4) * 2 * bhssd / 2


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv (the
    [B,H,S] log-sum-exp rows are left out). bf16."""
    bhsd = batch * cfg.n_heads * seq * cfg.head_dim
    return cfg.n_layers * (4 + 8) * bhsd * 2


def grad_allreduce_bytes(cfg) -> float:
    """bf16 gradient bytes a data-parallel step must reduce."""
    d, f = cfg.d_model, cfg.d_ff
    n = cfg.vocab_size * d + cfg.n_layers * (4 * d * d + 2 * d * f)
    return 2.0 * n


def kv_cache_bytes(cfg, batch: int, max_len: int) -> float:
    return 2.0 * cfg.n_layers * batch * cfg.n_heads * max_len \
        * cfg.head_dim * 2


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps=1e-6):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rotary(x, base=10000.0):
    """x [b, h, s, hd]; position p rotates the pair (x[i], x[i+hd/2]) by
    p / base**(2i/hd)."""
    import jax.numpy as jnp
    s, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_logits(params, tokens, n_heads: int):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p["embed"][tokens]
    b, s, d = x.shape
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for lay in p["layers"]:
        y = _rms_norm(x, lay["ln1"])
        q, k, v = jnp.split(y @ lay["wqkv"], 3, axis=-1)
        q, k, v = (t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        q, k = _rotary(q), _rotary(k)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
        x = x + a.transpose(0, 2, 1, 3).reshape(b, s, d) @ lay["wo"]
        y = _rms_norm(x, lay["ln2"])
        x = x + _gelu_tanh(y @ lay["w1"]) @ lay["w2"]
    x = _rms_norm(x, p["lnf"])
    head = p["head"] if "head" in p else p["embed"].T
    return x @ head


def reference_loss(params, tokens, targets, cfg):
    """Mean next-token cross entropy in float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(
        reference_logits(params, tokens, cfg.n_heads), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
