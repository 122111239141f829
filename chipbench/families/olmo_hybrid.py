"""Olmo-Hybrid family (ray_tpu.models.olmo_hybrid): config builder,
operation and byte counts, and a plain float32 reference of
Olmo-Hybrid-7B's layer equations (allenai/Olmo-Hybrid-7B config.json,
model_type olmo_hybrid; the linear-attention layers are Gated DeltaNet,
arXiv:2412.06464, with beta in (0, 2), arXiv:2411.12537; the
full-attention layers sit in OLMo 2's block, which norms what a branch
returns).

The equations (d 3840; linear attention: 30 heads, keys 96, values 192, 4
convolution taps and no bias; full attention: 30 heads x 128, MHA, no
positional encoding; vocabulary 100,352, untied head; eps 1e-6; no bias
and no multiplier anywhere):

    x_0 = E[tokens]
    layer l, kind = layer_types[l]:
      linear_attention:  h  = x + delta(rmsnorm(x; w_ln1))
                         x' = h + mlp(rmsnorm(h; w_ln2))
      full_attention:    h  = x + rmsnorm(attention(x); w_post_attention)
                         x' = h + rmsnorm(mlp(h); w_post_feedforward)
    mlp(y)       = (silu(y W_g) * (y W_u)) W_d         width 11008
    attention(x) : q = rmsnorm(x W_q; w_q), k = rmsnorm(x W_k; w_k) over
                   all 3,840 columns, v = x W_v, then 30 heads of 128;
                   a = causal softmax(q k^T / sqrt(128)) v; out = a W_o
    delta(y)     : [q | k | v] = silu(conv1d_causal(y W_in; w_c [11520, 4]))
                         depthwise, no bias, three zeros on the left
                   q -> [30, 96], k -> [30, 96], v -> [30, 192]
                   q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(96)   a head
                   k^ = k / sqrt(sum k^2 + 1e-6)
                   [a | b] = y W_ab                    30 each
                   beta_t = 2 sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t
                         + dt_bias) <= 0
                   S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k^_t)
                   S_t = S' + k^_t u_t^T;  o_t = S_t^T q^_t     S_0 = 0,
                         per head, S in R^{96 x 192}
                   o_t = rmsnorm_192(o_t; w_n [192]) * silu(y W_gate)
                         the norm a head first, THEN the gate
                   out = o W_out
    logits = rmsnorm(x_L; w_f) W_head
    loss   = cross entropy of the logits against the next token

The reference runs the delta rule TOKEN BY TOKEN (`lax.scan` over t, the
four lines above, no chunks, no inverse), attention as a plain masked
softmax, no kernel, no cache, and no code shared with ray_tpu. It reads the
program's parameter tree (`delta_in` is W_q | W_k | W_v side by side,
`delta_ab` W_a | W_b, `wkv` W_k | W_v). At long sequences it works in
blocks so that it fits beside the program's parameters: query blocks of at
most 512 against all keys, loss rows at most 2,048 at a time. The count
functions take the program's config object or the configuration file's
dict and import no jax: per-layer readers call them in run.py's parent
process, which must never initialise a backend."""

from __future__ import annotations

import contextlib
import functools
import importlib.machinery
import importlib.util
import math

# A tree from before the family (the parent of the PR that brought it)
# says so as the cell is looked up, in run.py's own process, before a
# cluster or a chip is touched (families/granite_hybrid.py tells how).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.olmo_hybrid", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models."
                      "olmo_hybrid: it cannot run an olmo_hybrid "
                      "configuration")

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three and ops/gated_delta.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_gd_fwd_kernel", "_gd_bwd_kernel")

_QUERY_BLOCK = 512
_LOSS_ROWS = 2048
_KERNEL_TOKENS = 2048       # kernel_errors: 32 chunks of 64


def build(config: dict, **overrides):
    """The program's OlmoHybridConfig at the file's sizes."""
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig

    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("linear_allow_neg_eigval", True)):
        if config[key] != want:
            raise ValueError(f"models/olmo_hybrid.py has {key} = {want!r} "
                             f"only, not {config[key]!r}")
    if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError(
            "families/olmo_hybrid.py's reference has no rotary (rope_theta "
            f"null only), not {config['rope_parameters']!r}")
    types = tuple(config["layer_types"])
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    if config["num_key_value_heads"] != config["num_attention_heads"] or \
            config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("models/olmo_hybrid.py has as many key heads as "
                         "query (value) heads only")
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              layer_types=types, d_ff=config["intermediate_size"],
              linear_num_heads=config["linear_num_value_heads"],
              linear_key_head_dim=config["linear_key_head_dim"],
              linear_value_head_dim=config["linear_value_head_dim"],
              linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
              norm_eps=config["rms_norm_eps"],
              max_seq_len=config["max_position_embeddings"])
    # not a key of config.json: the tiny stand-in's chunks of 8; the
    # program's own 64 where the file says nothing
    if "linear_chunk_size" in config:
        kw["linear_chunk_size"] = config["linear_chunk_size"]
    kw.update(overrides)
    return OlmoHybridConfig(**kw)


# The cell's second limit, on the delta rule itself: the MEAN of
# kernel_errors' eight relative errors (`held`). By the largest of the
# eight the two readings all but meet on the v5e (the program up to
# 0.0122, one seed's dg; the all-bfloat16 recurrence down to 0.0150,
# another seed's); by their mean they lie 1.8 times apart on the worst
# pair of seeds, and the limit sits between. The readings at the published
# head sizes are in the traffic file's `why_tolerance` and PERF.md
# section 4.
KERNEL_LIMIT = 0.008


def held(errors: dict) -> float:
    """What KERNEL_LIMIT is a limit on: the mean of a reading's errors
    (NaN where any is: a rule that diverges)."""
    return sum(errors.values()) / len(errors)


def hold_kernels(cfg):
    """Refuse a program whose delta rule is further from the recurrence
    than KERNEL_LIMIT: the loss at initialisation, which drivers/train.py
    compares, hardly sees the mixer at all (PERF.md section 4), so the cell
    holds the kernels to a limit of their own before it hands the program
    over."""
    from .. import harness

    errors = kernel_errors(cfg)
    _seeded_case.cache_clear()      # 0.2 GB of the chip the job needs
    # a NaN (a rule that diverges) is not <= the limit either
    harness.require(
        held(errors) <= KERNEL_LIMIT,
        f"the program's delta rule is off the float32 recurrence by "
        f"{held(errors):.3g} of the largest value, the mean over o, the "
        f"final state and six gradients (limit {KERNEL_LIMIT}): {errors}")


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    its delta-rule kernels held to KERNEL_LIMIT first where they are the
    chip's (elsewhere the rule is the jax.numpy form, or the kernels
    interpreted in float32, and tier-1 holds both to the recurrence at
    1e-4)."""
    import jax

    from ray_tpu.models.olmo_hybrid import (make_olmo_hybrid_train_step,
                                            olmo_hybrid_init,
                                            olmo_hybrid_loss)

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = make_olmo_hybrid_train_step(cfg, mesh=mesh,
                                                   rules=rules)
    return (lambda key: olmo_hybrid_init(key, cfg), init_state, step,
            lambda params, batch: olmo_hybrid_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _correction_dropped(rule, q, k, v, g, beta, chunk, initial_state=None):
    """u_t = beta_t v_t: the state is never read back before it is
    written, which is plain gated linear attention."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    b, _, H, K = q.shape

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = (jnp.exp(g_t)[..., None, None] * S
             + k_t[..., :, None] * (b_t[..., None] * v_t)[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S0 = jnp.zeros((b, H, K, v.shape[-1]), f32) if initial_state is None \
        else initial_state.astype(f32)
    S, o = jax.lax.scan(step, S0, tuple(
        t.astype(f32).swapaxes(0, 1) for t in (q, k, v, g, beta)))
    return o.swapaxes(0, 1).astype(v.dtype), S


def _beta_not_doubled(rule, q, k, v, g, beta, chunk, initial_state=None):
    """beta = sigmoid(.) in (0, 1): no negative eigenvalue."""
    return rule(q, k, v, g, beta / 2, chunk, initial_state)


def _decay_dropped(rule, q, k, v, g, beta, chunk, initial_state=None):
    """g = 0: the state never forgets (DeltaNet without its gate)."""
    return rule(q, k, v, g * 0, beta, chunk, initial_state)


def _chunk_carry_dropped(rule, q, k, v, g, beta, chunk, initial_state=None):
    """Every chunk starts from a zero state: chunks run as separate
    sequences, so only what crosses a boundary is lost."""
    b, L = q.shape[:2]

    def cut(t):
        return t.reshape(b * (L // chunk), chunk, *t.shape[2:])

    o, final = rule(cut(q), cut(k), cut(v), cut(g), cut(beta), chunk)
    final = final.reshape(b, L // chunk, *final.shape[1:])[:, -1]
    return o.reshape(v.shape), final


def _l2_norm_dropped(t, heads: int, scale: float, eps: float):
    """q and k as the convolution leaves them, cut into heads and scaled
    but not normalised (stands for models.decoder._unit_heads)."""
    b, L, _ = t.shape
    return (t.reshape(b, L, heads, -1) * scale).astype(t.dtype)


# What limit_readings.py plants in the program's delta rule, one at a
# time: faults of structure, each of which KERNEL_LIMIT catches on every
# seed (the traffic file's `why_tolerance` has the readings). The first
# four stand for the rule as models.decoder calls it, the last for the
# normalisation before it.
STRUCTURAL_FAULTS = {"correction_dropped": _correction_dropped,
                     "beta_not_doubled": _beta_not_doubled,
                     "decay_dropped": _decay_dropped,
                     "chunk_carry_dropped": _chunk_carry_dropped,
                     "l2_norm_dropped": _l2_norm_dropped}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault` in every linear-attention layer (a whole
    number of chunks, no cache): models.decoder calls the rule and the
    normalisation through its own names `gated_delta_rule` and
    `_unit_heads`, which stand for the faulty one meanwhile. Trace the
    program inside; a function jitted before keeps what it traced."""
    from ray_tpu.models import decoder

    name = "_unit_heads" if fault == "l2_norm_dropped" else "gated_delta_rule"
    real = getattr(decoder, name)
    setattr(decoder, name, STRUCTURAL_FAULTS[fault] if name == "_unit_heads"
            else functools.partial(STRUCTURAL_FAULTS[fault], real))
    try:
        yield
    finally:
        setattr(decoder, name, real)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's OlmoHybridConfig or the configuration's
    dict."""
    if isinstance(cfg, dict):
        types = cfg["layer_types"]
        s = dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                 v=cfg["vocab_size"], H=cfg["linear_num_value_heads"],
                 K=cfg["linear_key_head_dim"],
                 V=cfg["linear_value_head_dim"],
                 taps=cfg["linear_conv_kernel_dim"])
    else:
        types = cfg.layer_types
        s = dict(d=cfg.d_model, f=cfg.d_ff, v=cfg.vocab_size,
                 H=cfg.linear_num_heads, K=cfg.linear_key_head_dim,
                 V=cfg.linear_value_head_dim,
                 taps=cfg.linear_conv_kernel_dim)
    s["linear_layers"] = sum(t == "linear_attention" for t in types)
    s["full_layers"] = len(types) - s["linear_layers"]
    return s


def _recurrence_flops_per_token(s: dict) -> float:
    """The three K x V products a token and head that the recurrence
    itself has (S'^T k, k u^T, S^T q): 6 K V, whatever the chunking."""
    return 6.0 * s["K"] * s["V"] * s["H"]


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul and convolution operations one token needs in the forward
    pass at context `seq`. A linear-attention layer: the projections to
    q | k | v, the gate and a | b, the convolution's taps, the
    recurrence's three products a head (no chunking overhead: no inverse,
    no tiles), the output projection. A full-attention layer: q, k, v, o
    and causal attention (QK^T and PV over half the square). Every layer:
    the SwiGLU MLP. The untied head once."""
    s = _dims(cfg)
    d, H = s["d"], s["H"]
    conv_dim = H * (2 * s["K"] + s["V"])
    mlp = 3 * 2 * d * s["f"]
    linear = (2 * d * (conv_dim + H * s["V"] + 2 * H) + 2 * s["taps"] * conv_dim
              + _recurrence_flops_per_token(s) + 2 * H * s["V"] * d + mlp)
    full = 4 * 2 * d * d + 2 * 2 * seq * d / 2 + mlp
    return (s["linear_layers"] * linear + s["full_layers"] * full
            + 2 * d * s["v"])


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the kernels' tiles and inverses made again in their backward) is not
    counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step,
    the full-attention layers only: forward 2 matmuls, backward 4, each
    2*B*H*S*S*D, halved for the causal mask."""
    s = _dims(cfg)
    return s["full_layers"] * (2 + 4) * 2 * batch * seq * seq * s["d"] / 2


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv. MHA,
    bf16."""
    s = _dims(cfg)
    return s["full_layers"] * (4 + 8) * batch * seq * s["d"] * 2


def gated_delta_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the delta-rule kernels in one train step,
    the linear-attention layers only: the recurrence's three K x V
    products a token and head forward and twice that backward. The least
    any chunking needs: no inverse, no [C, C] tile and nothing made again
    is counted, so the share does not go stale when the chunk or the way
    T is made changes."""
    s = _dims(cfg)
    return (s["linear_layers"] * 3.0 * batch * seq
            * _recurrence_flops_per_token(s))


def gated_delta_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: q, k, v, g, beta and dO read
    and o, dq, dk, dv, dg, dbeta written once, in the dtypes the program
    passes them (q, k, v, o and their gradients bf16; g, beta and theirs
    float32). No state a chunk and nothing read twice is counted."""
    s = _dims(cfg)
    token = (4 * s["H"] * s["K"] * 2 + 4 * s["H"] * s["V"] * 2
             + 4 * s["H"] * 4)
    return s["linear_layers"] * batch * seq * token


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _l2(t, eps):
    import jax.numpy as jnp
    return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + eps)


def _blocks(n: int, limit: int) -> int:
    """The largest block size up to `limit` that divides n."""
    return max(b for b in range(1, min(n, limit) + 1) if n % b == 0)


def _attention(x, lay, cfg):
    """x [b, s, d] -> [b, s, d]: q and k RMS-normed over all their
    columns, no rotary, scores over sqrt(head_dim); query blocks against
    all keys."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    k, v = jnp.split(x @ lay["wkv"], 2, axis=-1)
    q = _rms_norm(x @ lay["wq"], lay["q_norm"], cfg.norm_eps)
    k = _rms_norm(k, lay["k_norm"], cfg.norm_eps).astype(x.dtype)
    q = q.astype(x.dtype).reshape(b, s, h, hd)
    k, v = k.reshape(b, s, h, hd), v.reshape(b, s, h, hd)
    block = _blocks(s, _QUERY_BLOCK)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, first = args                       # [b, block, h, hd]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * hd ** -0.5
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    out = jax.lax.map(one_block, (
        q.reshape(b, s // block, block, h, hd).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, d) @ lay["wo"]


def recurrence(q, k, v, g, beta, initial_state=None):
    """The gated delta rule as it is defined, one token after another:
    S' = exp(g_t) S_{t-1}; u_t = beta_t (v_t - S'^T k_t); S_t = S' + k_t
    u_t^T; o_t = S_t^T q_t. q, k [b, s, H, K] (normalised by the caller),
    v [b, s, H, V], g, beta [b, s, H]. Returns (o [b, s, H, V], the final
    state [b, H, K, V]), in v's dtype, which the state has at every
    step."""
    import jax
    import jax.numpy as jnp

    dtype = v.dtype
    b, s, H, K = q.shape

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = (jnp.exp(g_t)[..., None, None] * S).astype(dtype)
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = (S + k_t[..., :, None] * u.astype(dtype)[..., None, :]
             ).astype(dtype)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t).astype(dtype)

    # Memory only, not mathematics: the tokens go through the same `step`
    # one after another, 64 to a jax.checkpoint, so that a gradient keeps
    # one state a block and makes the block's again, where a plain scan
    # keeps three a token (13.6 GB at 2,048 tokens of 30 x 96 x 192).
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


def _all_of(fn, wo, ws):
    """o, the final state and the six gradients of a weighted sum of both,
    one program."""
    import jax
    import jax.numpy as jnp

    def scalar(*given):
        o, state = fn(*given)
        return (jnp.sum(o.astype(jnp.float32) * wo)
                + jnp.sum(state.astype(jnp.float32) * ws)), (o, state)

    def run(*given):
        (_, (o, state)), grads = jax.value_and_grad(
            scalar, argnums=tuple(range(6)), has_aux=True)(*given)
        return (o, state, *grads)
    return jax.jit(run)


def _by_recurrence(cfg, dtype):
    """`_l2` and `recurrence` over kernel_errors' six inputs, in `dtype`."""
    H, K = cfg.linear_num_heads, cfg.linear_key_head_dim

    def fn(*given):
        q, k, v, g, beta, init = (t.astype(dtype) for t in given)
        heads = (*q.shape[:2], H, K)
        q = (_l2(q.reshape(heads), cfg.norm_eps) * K ** -0.5).astype(dtype)
        k = _l2(k.reshape(heads), cfg.norm_eps).astype(dtype)
        return recurrence(q, k, v, g, beta, init)
    return fn


@functools.lru_cache(maxsize=1)
def _seeded_case(cfg, seed: int):
    """kernel_errors' inputs for a seed and what the float32 recurrence
    makes of them (2,048 dependent steps and their gradients: half a
    minute on the chip, so limit_readings.py's seven readings a seed share
    one). -> (the six inputs, the weights of o and of the state, want)"""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    H, K, V = (cfg.linear_num_heads, cfg.linear_key_head_dim,
               cfg.linear_value_head_dim)
    chunk = cfg.linear_chunk_size
    # the tiny stand-in's chunks of 8: four of them
    b, L = 1, _KERNEL_TOKENS // chunk * chunk if chunk >= 64 else 4 * chunk
    normal = jax.random.normal

    @jax.jit
    def seeded(key):
        ks = jax.random.split(key, 10)
        step0 = jnp.exp(jax.random.uniform(
            ks[0], (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        rate = jax.random.uniform(ks[1], (H,), minval=1.0, maxval=16.0)
        inputs = (
            jax.nn.silu(normal(ks[2], (b, L, H * K))).astype(cfg.dtype),
            jax.nn.silu(normal(ks[3], (b, L, H * K))).astype(cfg.dtype),
            jax.nn.silu(normal(ks[4], (b, L, H, V))).astype(cfg.dtype),
            -rate * jax.nn.softplus(normal(ks[5], (b, L, H)) + step0
                                    + jnp.log(-jnp.expm1(-step0))),      # g
            2.0 * jax.nn.sigmoid(normal(ks[6], (b, L, H))),           # beta
            normal(ks[7], (b, H, K, V)))                    # initial state
        return (inputs, normal(ks[8], (b, L, H, V)),
                normal(ks[9], (b, H, K, V)))

    inputs, wo, ws = seeded(jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        want = _all_of(_by_recurrence(cfg, f32), wo, ws)(
            *(t.astype(f32) for t in inputs))
    return inputs, wo, ws, want


def kernel_errors(cfg, seed: int = 0, low: bool = False) -> dict:
    """The program's normalisation and delta rule, as models.decoder calls
    them (on a TPU the two kernels), against `_l2` and `recurrence` in
    float32 at the configuration's head sizes: 2,048 tokens from a seeded
    initial state, inputs as a linear-attention layer makes them at
    initialisation (q, k, v silu of a normal in the model's dtype, g =
    -U[1, 16] softplus(N(0, 1) + the seeded bias), beta = 2 sigmoid(N(0,
    1))). For o, the final state and the gradient of a seeded weighted sum
    of both by each of q, k, v, g, beta and the initial state: the largest
    |got - want| over the largest |want|. With `low`, what is compared is
    the reference itself with every input, decay and state in bfloat16:
    the second reading KERNEL_LIMIT lies under."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    f32 = jnp.float32
    H, K = cfg.linear_num_heads, cfg.linear_key_head_dim
    inputs, wo, ws, want = _seeded_case(cfg, seed)

    def by_program(q, k, v, g, beta, init):
        return decoder.gated_delta_rule(
            decoder._unit_heads(q, H, K ** -0.5, cfg.norm_eps),
            decoder._unit_heads(k, H, 1.0, cfg.norm_eps), v, g, beta,
            cfg.linear_chunk_size, init)

    if low:
        with jax.default_matmul_precision("highest"):
            got = _all_of(_by_recurrence(cfg, jnp.bfloat16), wo, ws)(
                *(t.astype(f32) for t in inputs))
    else:       # as it runs: no precision asked
        got = _all_of(by_program, wo, ws)(*inputs)
    errors = jax.jit(lambda got, want: [
        jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
        / jnp.max(jnp.abs(w.astype(f32))) for g, w in zip(got, want)])
    names = ("o", "state", "dq", "dk", "dv", "dg", "dbeta", "dinit")
    return dict(zip(names, map(float, errors(got, want)), strict=True))


def _linear_attention(y, lay, cfg):
    """y [b, s, d] -> (out [b, s, d], the final state [b, H, K, V]): the
    delta rule token by token."""
    import jax.numpy as jnp

    b, s, _ = y.shape
    H, K, V = (cfg.linear_num_heads, cfg.linear_key_head_dim,
               cfg.linear_value_head_dim)
    taps = cfg.linear_conv_kernel_dim
    dtype = y.dtype
    qkv = y @ lay["delta_in"]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = _silu(sum(padded[:, k:k + s] * lay["conv_w"][:, k]
                    for k in range(taps)))
    q, k, v = jnp.split(qkv, [H * K, 2 * H * K], axis=-1)
    q = (_l2(q.reshape(b, s, H, K), cfg.norm_eps) * K ** -0.5).astype(dtype)
    k = _l2(k.reshape(b, s, H, K), cfg.norm_eps).astype(dtype)
    a, bt = jnp.split(y @ lay["delta_ab"], 2, axis=-1)
    # sigmoid, softplus, the decay and the state in the working precision
    # (float32 unless the all-bfloat16 reading asks otherwise)
    beta = (2.0 / (1.0 + jnp.exp(-bt))).astype(dtype)
    g = (-jnp.exp(lay["A_log"]) * jnp.logaddexp(a + lay["dt_bias"], 0.0)
         ).astype(dtype)
    o, S = recurrence(q, k, v.reshape(b, s, H, V), g, beta)
    o = _rms_norm(o, lay["delta_norm"], cfg.norm_eps).reshape(b, s, H * V)
    out = (o * _silu(y @ lay["delta_gate"])).astype(dtype)
    return out @ lay["delta_out"], S


def _mlp(y, lay):
    return (_silu(y @ lay["w_gate"]) * (y @ lay["w_up"])) @ lay["w_down"]


def _hidden(params, tokens, cfg, dtype=None):
    """(final-norm rows [b, s, d], W_head [d, V], the linear-attention
    layers' final states), every parameter and so every value in `dtype`
    (float32 unless given)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    x = p["embed"][tokens]
    eps, states = cfg.norm_eps, []
    for lay in p["layers"]:
        if "delta_in" in lay:
            out, state = _linear_attention(_rms_norm(x, lay["ln1"], eps),
                                           lay, cfg)
            states.append(state)
            h = x + out
            x = h + _mlp(_rms_norm(h, lay["ln2"], eps), lay)
        else:
            h = x + _rms_norm(_attention(x, lay, cfg), lay["post_attention"],
                              eps)
            x = h + _rms_norm(_mlp(h, lay), lay["post_feedforward"], eps)
        x = x.astype(p["embed"].dtype)
    return _rms_norm(x, p["lnf"], eps), p["head"], states


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    x, head, _ = _hidden(params, tokens, cfg)
    return x @ head


def reference_final_states(params, tokens, cfg):
    """The state [b, H, K, V] each linear-attention layer is left in, in
    layer order, float32."""
    return _hidden(params, tokens, cfg)[2]


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """Mean next-token cross entropy, in float32, the logits a block of
    rows at a time. `dtype` is for setting the comparison's limit only:
    the same reference with every parameter and value (the state and the
    decays too) in a lower precision (bfloat16) has to come out as not
    correct (PERF.md)."""
    import jax
    import jax.numpy as jnp

    x, head, _ = _hidden(params, tokens, cfg, dtype)
    rows = x.reshape(-1, x.shape[-1])
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), -1)
        return jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1))

    total = jax.lax.map(one_block, (rows.reshape(-1, block, rows.shape[-1]),
                                    targets.reshape(-1, block)))
    return -jnp.sum(total) / targets.size
