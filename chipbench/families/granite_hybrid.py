"""Granite-4.0-H family (ray_tpu.models.hybrid): config builder, operation
and byte counts, and a plain float32 reference of granite-4.0-h-micro's
layer equations (ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid with no routed experts; HF modeling_granitemoehybrid.py,
whose Mamba-2 layer is the Mamba-2 paper's, arXiv:2405.21060).

The equations (d 2048; Mamba-2: inner 4096 = 64 heads x 64, state 128, one
group, 4 convolution taps with bias; attention: 32 query heads over 8 kv
heads x 64, NO positional encoding; vocabulary 100,352, tied head):

    x_0 = 12 E[tokens]                                 embedding_multiplier
    layer l, kind = layer_types[l]:
      h  = x + 0.22 mixer_l(rmsnorm(x; w_ln1, 1e-5))   residual_multiplier
      x' = h + 0.22 mlp(rmsnorm(h; w_ln2, 1e-5))
    mlp(y)       = (silu(y W_g) * (y W_u)) W_d         width 8192
    attention(y) : q = y W_q -> [32, 64]; k | v = y W_kv -> [8, 64] each
                   a = causal softmax(0.015625 q k^T) v, kv head j serving
                   query heads 4j..4j+3; out = a W_o
    mamba2(y)    : [z | xBC | dt] = y W_in             W_in [2048, 8512]
                   xBC = silu(conv1d_causal(xBC; w_c [4352, 4], b_c))
                         depthwise, three zeros on the left
                   [x | B | C] = xBC     x -> [64 heads, 64]; B, C in R^128
                   D_t = softplus(dt_t + dt_bias) in R^64;  a = -exp(A_log)
                   S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t   S_0 = 0,
                         per head, S in R^{64 x 128}
                   y_t = S_t C_t + D x_t
                   out = rmsnorm(y * silu(z); w_n [4096], 1e-5) W_out
                         the gate first, then ONE norm over all 4,096
    logits = rmsnorm(x_L; w_f) E^T / 8                 logits_scaling
    loss   = cross entropy of the logits against the next token

The reference runs the recurrence TOKEN BY TOKEN (`lax.scan` over t, no
chunks), attention as a plain masked softmax, no kernel, no cache, and no
code shared with ray_tpu. It reads the program's parameter tree (`wkv` is
W_k | W_v side by side; `w_gate`, `w_up` are the two halves of HF's
input_linear). At long sequences it works in blocks so that it fits
beside the program's parameters: query blocks of at most 1,024 against
all keys, loss rows at most 2,048 at a time. The count functions take
the program's config object or the configuration file's dict and import
no jax: per-layer readers call them in run.py's parent process, which
must never initialise a backend."""

from __future__ import annotations

import contextlib
import importlib.machinery
import importlib.util

# A tree from before the family (the parent of the PR that brought it)
# says so as the cell is looked up, in run.py's own process, before a
# cluster or a chip is touched. The module is looked for where the import
# system would, in the package's own locations, without importing the
# package: ray_tpu.models imports jax, which that process never does.
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.hybrid", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models.hybrid: "
                      "it cannot run a granite-hybrid configuration")

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three and ops/ssm_scan.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_ssm_fwd_kernel", "_ssm_bwd_kernel")

_QUERY_BLOCK = 1024
_LOSS_ROWS = 2048


def build(config: dict, **overrides):
    """The program's HybridConfig at the file's sizes."""
    from ray_tpu.models.hybrid import HybridConfig

    for key, want in (("position_embedding_type", "nope"),
                      ("num_local_experts", 0), ("tie_word_embeddings", True),
                      ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm")):
        if config[key] != want:
            raise ValueError(f"models/hybrid.py has {key} = {want!r} only, "
                             f"not {config[key]!r}")
    types = tuple(config["layer_types"])
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    if (config["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("mamba heads x head size is not expand x hidden")
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              n_kv_heads=config["num_key_value_heads"],
              layer_types=types,
              d_ff=config["shared_intermediate_size"],
              mamba_n_heads=config["mamba_n_heads"],
              mamba_d_head=config["mamba_d_head"],
              mamba_d_state=config["mamba_d_state"],
              mamba_n_groups=config["mamba_n_groups"],
              mamba_d_conv=config["mamba_d_conv"],
              mamba_chunk_size=config["mamba_chunk_size"],
              attention_multiplier=config["attention_multiplier"],
              embedding_multiplier=float(config["embedding_multiplier"]),
              residual_multiplier=config["residual_multiplier"],
              logits_scaling=float(config["logits_scaling"]),
              norm_eps=config["rms_norm_eps"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    return HybridConfig(**kw)


# The cell's second limit, on the scan itself: the largest of
# kernel_errors' nine relative errors. Two readings on the v5e at the
# published head sizes, 16 seeds (limit_readings.py; PERF.md section 4):
# the program's kernels 0.0045 to 0.0132; `recurrence` with every input,
# decay and state in bfloat16, the nearest precision below, 0.035 to 0.29.
# Every structural fault below reads 0.5 or more on every seed.
KERNEL_LIMIT = 0.02


def hold_kernels(cfg):
    """Refuse a program whose scan is further from the recurrence than
    KERNEL_LIMIT: the loss at initialisation, which drivers/train.py
    compares, cannot see the scan's precision at all and its structure
    hardly (PERF.md section 4), so the cell holds the scan to a limit of
    its own before it hands the program over."""
    from .. import harness

    errors = kernel_errors(cfg)
    worst = max(errors, key=errors.get)
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program's scan is off the float32 recurrence by "
        f"{errors[worst]:.3g} of the largest value in {worst} (limit "
        f"{KERNEL_LIMIT}): {errors}")


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    its scan kernels held to KERNEL_LIMIT first where they are the chip's
    (elsewhere the scan is the jax.numpy form, or the kernels interpreted
    in float32, and tier-1 holds both to the recurrence at 1e-4)."""
    import jax

    from ray_tpu.models.hybrid import (hybrid_init, hybrid_loss,
                                       make_hybrid_train_step)

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = make_hybrid_train_step(cfg, mesh=mesh, rules=rules)
    return (lambda key: hybrid_init(key, cfg), init_state, step,
            lambda params, batch: hybrid_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _state_part_zeroed(scan, x, dt, a, B, C, D, chunk, initial_state=None):
    """y = D x alone: nothing of the state reaches the output."""
    _, final = scan(x, dt, a, B, C, D, chunk, initial_state)
    return (D[:, None] * x).astype(x.dtype), final


def _chunk_carry_dropped(scan, x, dt, a, B, C, D, chunk, initial_state=None):
    """Every chunk starts from a zero state: chunks scanned as separate
    sequences, so only what crosses a boundary is lost."""
    b, L = x.shape[:2]

    def cut(t):
        return t.reshape(b * (L // chunk), chunk, *t.shape[2:])

    y, final = scan(cut(x), cut(dt), a, cut(B), cut(C), D, chunk)
    final = final.reshape(b, L // chunk, *final.shape[1:])[:, -1]
    return y.reshape(x.shape), final


def _decay_rate_halved(scan, x, dt, a, B, C, D, chunk, initial_state=None):
    """exp(dt a / 2): every head forgets half as fast."""
    return scan(x, dt, a / 2, B, C, D, chunk, initial_state)


def _state_bfloat16_between_chunks(scan, x, dt, a, B, C, D, chunk,
                                   initial_state=None):
    """A fault of precision, not of structure: the state a chunk hands
    the next is rounded to bfloat16 (64 roundings a 16,384-token
    sequence), everything inside a chunk as it is."""
    import jax
    import jax.numpy as jnp

    b, L, H, P = x.shape

    def by_chunk(t):
        return t.reshape(b, L // chunk, chunk, *t.shape[2:]).swapaxes(0, 1)

    state = (jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
             if initial_state is None else initial_state)

    def one(state, t):
        y, state = scan(*t[:2], a, *t[2:], D, chunk, state)
        # reduce_precision, not a pair of casts: XLA:TPU may keep the
        # excess precision of a float32 -> bfloat16 -> float32 round trip
        return jax.lax.reduce_precision(state, 8, 7), y

    state, ys = jax.lax.scan(one, state, tuple(map(by_chunk, (x, dt, B, C))))
    return ys.swapaxes(0, 1).reshape(x.shape), state


# What limit_readings.py plants in the program's scan, one at a time. The
# first three are faults of structure: KERNEL_LIMIT catches each on every
# seed, the mix's reference_loss_tolerance the first and the third on
# about half the seeds and the second on none. The last is one of
# precision, which neither limit sees (PERF.md section 4 has the tables).
STRUCTURAL_FAULTS = {"state_part_zeroed": _state_part_zeroed,
                     "chunk_carry_dropped": _chunk_carry_dropped,
                     "decay_rate_halved": _decay_rate_halved}
PRECISION_FAULTS = {
    "state_bfloat16_between_chunks": _state_bfloat16_between_chunks}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault` in every Mamba-2 layer's scan (a whole
    number of chunks, no cache): models.decoder calls the scan through its
    own name `ssm_scan`, which stands for the faulty one meanwhile. Trace
    the program inside; a function jitted before keeps what it traced."""
    import functools

    from ray_tpu.models import decoder

    real = decoder.ssm_scan
    decoder.ssm_scan = functools.partial(
        {**STRUCTURAL_FAULTS, **PRECISION_FAULTS}[fault], real)
    try:
        yield
    finally:
        decoder.ssm_scan = real


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's HybridConfig or the configuration's dict."""
    if isinstance(cfg, dict):
        types = cfg["layer_types"]
        s = dict(d=cfg["hidden_size"], f=cfg["shared_intermediate_size"],
                 v=cfg["vocab_size"], kv=cfg["num_key_value_heads"],
                 hd=cfg["hidden_size"] // cfg["num_attention_heads"],
                 H=cfg["mamba_n_heads"], P=cfg["mamba_d_head"],
                 N=cfg["mamba_d_state"], G=cfg["mamba_n_groups"],
                 K=cfg["mamba_d_conv"], Q=cfg["mamba_chunk_size"])
    else:
        types = cfg.layer_types
        s = dict(d=cfg.d_model, f=cfg.d_ff, v=cfg.vocab_size,
                 kv=cfg.n_kv_heads, hd=cfg.head_dim, H=cfg.mamba_n_heads,
                 P=cfg.mamba_d_head, N=cfg.mamba_d_state,
                 G=cfg.mamba_n_groups, K=cfg.mamba_d_conv,
                 Q=cfg.mamba_chunk_size)
    s["mamba_layers"] = sum(t == "mamba" for t in types)
    s["attention_layers"] = len(types) - s["mamba_layers"]
    return s


def _scan_flops_per_token(s: dict) -> float:
    """The chunked scan's four products for one token of one layer, the
    causal half of the two [Q, Q] ones: C B^T (once a group), the decayed
    scores times dt x, the chunk's state, and y from the carried state."""
    inner = s["H"] * s["P"]
    return (s["Q"] * s["N"] * s["G"] + s["Q"] * inner
            + 2 * 2 * inner * s["N"])


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul and convolution operations one token needs in the forward
    pass at context `seq`. A Mamba-2 layer: input and output projections,
    the convolution's taps, the chunked scan's four products. An attention
    layer: q, k | v, o and causal attention (QK^T and PV over half the
    square). Every layer: the SwiGLU MLP. The tied head once."""
    s = _dims(cfg)
    d, inner = s["d"], s["H"] * s["P"]
    conv_dim = inner + 2 * s["G"] * s["N"]
    mlp = 3 * 2 * d * s["f"]
    mamba = (2 * d * (inner + conv_dim + s["H"]) + 2 * s["K"] * conv_dim
             + _scan_flops_per_token(s) + 2 * inner * d + mlp)
    attention = (2 * d * d + 2 * d * 2 * s["kv"] * s["hd"] + 2 * d * d
                 + 2 * 2 * seq * d / 2 + mlp)
    return (s["mamba_layers"] * mamba + s["attention_layers"] * attention
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
    return s["attention_layers"] * (2 + 4) * 2 * batch * seq * seq * s["d"] / 2


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv, k and v
    counted at their 8 heads, not their copies across a group. bf16."""
    s = _dims(cfg)
    q = batch * seq * s["d"] * 2
    kv = batch * seq * s["kv"] * s["hd"] * 2
    return s["attention_layers"] * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def ssm_scan_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the scan kernels in one train step, the
    Mamba-2 layers only: the forward's four products and twice that for
    their gradients. Exponentials, masks and the elementwise decays are
    not operations a roofline counts."""
    s = _dims(cfg)
    return s["mamba_layers"] * 3.0 * batch * seq * _scan_flops_per_token(s)


def ssm_scan_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: the forward reads x, dt, B, C
    and writes y; the backward reads x, dt, B, C, dy and writes dx, d dt,
    dB, dC; one float32 state a chunk is written once and read once. x, y,
    B, C and their gradients bf16, dt float32. The running sums, y read
    again by the backward and the gradient's second dt-shaped array are
    left out: fewer bytes, never more."""
    s = _dims(cfg)
    inner, bc = s["H"] * s["P"], 2 * s["G"] * s["N"]
    token = (2 * inner * 2 + s["H"] * 4 + bc * 2              # forward
             + 3 * inner * 2 + 2 * s["H"] * 4 + 2 * bc * 2)   # backward
    states = 2 * (seq // s["Q"]) * inner * s["N"] * 4
    return s["mamba_layers"] * batch * (seq * token + states)


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _blocks(n: int, limit: int) -> int:
    """The largest block size up to `limit` that divides n."""
    return max(b for b in range(1, min(n, limit) + 1) if n % b == 0)


def _attention(y, lay, cfg):
    """y [b, s, d] -> [b, s, d]: no rotary, scores scaled by
    attention_multiplier, each kv head serving its group of query heads;
    query blocks against all keys."""
    import jax
    import jax.numpy as jnp

    b, s, d = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (y @ lay["wq"]).reshape(b, s, kvh, h // kvh, hd)
    k, v = jnp.split(y @ lay["wkv"], 2, axis=-1)
    k, v = k.reshape(b, s, kvh, hd), v.reshape(b, s, kvh, hd)
    block = _blocks(s, _QUERY_BLOCK)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, first = args                       # [b, block, kvh, group, hd]
        sc = jnp.einsum("bqjgd,bkjd->bjgqk", qb, k) * cfg.attention_multiplier
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("bjgqk,bkjd->bqjgd", p.astype(v.dtype), v)

    out = jax.lax.map(one_block, (
        q.reshape(b, s // block, block, kvh, h // kvh, hd).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, d) @ lay["wo"]


def recurrence(x, delta, a, B, C, skip, initial_state=None):
    """The selective scan as it is defined, one token after another:
    S_t = exp(delta_t a) S_{t-1} + delta_t x_t (x) B_t;  y_t = S_t C_t +
    skip x_t. x [b, s, H, P], delta [b, s, H], a, skip [H], B, C
    [b, s, H, N] (a group's B and C already given to each of its heads).
    Returns (y [b, s, H, P], the final state [b, H, P, N]), in x's dtype."""
    import jax
    import jax.numpy as jnp

    dtype = x.dtype
    b, _, H, P = x.shape

    def step(S, t):
        x_t, d_t, B_t, C_t = t                 # [b,H,P] [b,H] [b,H,N] [b,H,N]
        S = (jnp.exp(d_t * a)[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
             ).astype(dtype)
        y_t = jnp.einsum("bhpn,bhn->bhp", S, C_t) + skip[:, None] * x_t
        return S, y_t.astype(dtype)

    if initial_state is None:
        initial_state = jnp.zeros((b, H, P, B.shape[-1]), dtype)
    S, ys = jax.lax.scan(
        step, initial_state,
        (x.swapaxes(0, 1), delta.swapaxes(0, 1), B.swapaxes(0, 1),
         C.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), S


def kernel_errors(cfg, seed: int = 0, low: bool = False) -> dict:
    """The program's scan, as models.decoder calls it (on a TPU its two
    kernels), against `recurrence` in float32 at the configuration's head
    sizes: four chunks from a seeded initial state, inputs as a Mamba-2
    layer makes them at initialisation (x, B, C in the model's dtype, the
    steps softplus(N(0, 1) + the seeded bias), a = -U[1, 16], D = 1). For
    y, the final state and the gradient of a seeded weighted sum of both
    by each of x, dt, a, B, C, D and the initial state: the largest
    |got - want| over the largest |want|. With `low`, what is compared is
    `recurrence` itself with every input, decay and state in bfloat16:
    the second reading KERNEL_LIMIT lies under."""
    import math

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    f32 = jnp.float32
    H, P, N, G, Q = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                     cfg.mamba_n_groups, cfg.mamba_chunk_size)
    b, L = 1, 4 * Q
    normal = jax.random.normal

    @jax.jit
    def seeded(key):
        """(the scan's seven inputs, the same values in float32, the
        weights of y and of the state)"""
        ks = jax.random.split(key, 9)
        step0 = jnp.exp(jax.random.uniform(
            ks[0], (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        inputs = (
            (0.5 * normal(ks[1], (b, L, H, P))).astype(cfg.dtype),       # x
            jax.nn.softplus(normal(ks[2], (b, L, H))
                            + step0 + jnp.log(-jnp.expm1(-step0))),     # dt
            -jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0),  # a
            normal(ks[4], (b, L, G, N)).astype(cfg.dtype),              # B
            normal(ks[5], (b, L, G, N)).astype(cfg.dtype),              # C
            jnp.ones((H,), f32),                                        # D
            normal(ks[6], (b, H, P, N)))                    # initial state
        return (inputs, tuple(t.astype(f32) for t in inputs),
                normal(ks[7], (b, L, H, P)), normal(ks[8], (b, H, P, N)))

    inputs, exact, wy, ws = seeded(jax.random.PRNGKey(seed))

    def by_program(x, dt, a, B, C, D, init):
        return decoder.ssm_scan(x, dt, a, B, C, D, Q, init)

    def by_recurrence(dtype):
        def fn(*given):
            x, dt, a, B, C, D, init = (t.astype(dtype) for t in given)
            return recurrence(x, dt, a, jnp.repeat(B, H // G, 2),
                              jnp.repeat(C, H // G, 2), D, init)
        return fn

    def all_of(fn):
        """y, the final state and the seven gradients, one program."""
        def scalar(*given):
            y, state = fn(*given)
            return (jnp.sum(y.astype(f32) * wy)
                    + jnp.sum(state.astype(f32) * ws)), (y, state)

        def run(*given):
            (_, (y, state)), grads = jax.value_and_grad(
                scalar, argnums=tuple(range(7)), has_aux=True)(*given)
            return (y, state, *grads)
        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        want = all_of(by_recurrence(f32))(*exact)
        if low:
            got = all_of(by_recurrence(jnp.bfloat16))(*exact)
    if not low:
        got = all_of(by_program)(*inputs)   # as it runs: no precision asked
    errors = jax.jit(lambda got, want: [
        jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
        / jnp.max(jnp.abs(w.astype(f32))) for g, w in zip(got, want)])
    names = ("y", "state", "dx", "ddt", "da", "dB", "dC", "dD", "dinit")
    return dict(zip(names, map(float, errors(got, want)), strict=True))


def _mamba2(y, lay, cfg):
    """y [b, s, d] -> (out [b, s, d], the final state [b, H, P, N]): the
    recurrence token by token."""
    import jax
    import jax.numpy as jnp

    b, s, _ = y.shape
    H, P, N, G = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                  cfg.mamba_n_groups)
    inner, taps = H * P, cfg.mamba_d_conv
    z, xbc, dt = jnp.split(y @ lay["in_proj"],
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = lay["conv_b"] + sum(
        padded[:, k:k + s] * lay["conv_w"][:, k] for k in range(taps))
    xbc = _silu(conv)
    x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    x = x.reshape(b, s, H, P)
    B = jnp.repeat(B.reshape(b, s, G, N), H // G, axis=2)
    C = jnp.repeat(C.reshape(b, s, G, N), H // G, axis=2)
    # softplus, the decay and the state in the working precision (float32
    # unless the all-bfloat16 reading asks otherwise)
    dtype = y.dtype
    delta = jnp.logaddexp(dt + lay["dt_bias"], 0.0).astype(dtype)
    ys, S = recurrence(x, delta, -jnp.exp(lay["A_log"]).astype(dtype), B, C,
                       lay["D"].astype(dtype))
    ys = ys.reshape(b, s, inner)
    out = _rms_norm(ys * _silu(z), lay["ssm_norm"], cfg.norm_eps)
    return out @ lay["out_proj"], S


def _hidden(params, tokens, cfg, dtype=None):
    """(final-norm rows [b, s, d], E [V, d], the Mamba-2 layers' final
    states), every parameter and so every value in `dtype` (float32 unless
    given)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    x = cfg.embedding_multiplier * p["embed"][tokens]
    x = x.astype(p["embed"].dtype)
    eps, res = cfg.norm_eps, cfg.residual_multiplier
    states = []
    for lay in p["layers"]:
        y = _rms_norm(x, lay["ln1"], eps)
        if "in_proj" in lay:
            out, state = _mamba2(y, lay, cfg)
            states.append(state)
        else:
            out = _attention(y, lay, cfg)
        h = x + res * out
        y = _rms_norm(h, lay["ln2"], eps)
        x = h + res * ((_silu(y @ lay["w_gate"]) * (y @ lay["w_up"]))
                       @ lay["w_down"])
        x = x.astype(p["embed"].dtype)
    return _rms_norm(x, p["lnf"], eps), p["embed"], states


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    x, embed, _ = _hidden(params, tokens, cfg)
    return x @ embed.T / cfg.logits_scaling


def reference_final_states(params, tokens, cfg):
    """The state [b, H, P, N] each Mamba-2 layer is left in, in layer
    order, float32."""
    return _hidden(params, tokens, cfg)[2]


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """Mean next-token cross entropy, in float32, the logits a block of
    rows at a time. `dtype` is for setting the comparison's limit only:
    the same reference with every parameter and value (the state and the
    decays too) in a lower precision (bfloat16) has to come out as not
    correct (PERF.md)."""
    import jax
    import jax.numpy as jnp

    x, embed, _ = _hidden(params, tokens, cfg, dtype)
    rows = x.reshape(-1, x.shape[-1])
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb = args
        logits = xb @ embed.T / cfg.logits_scaling
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1))

    total = jax.lax.map(one_block, (rows.reshape(-1, block, rows.shape[-1]),
                                    targets.reshape(-1, block)))
    return -jnp.sum(total) / targets.size
