"""SambaY family (ray_tpu.models.sambay): config builder, operation and
byte counts, and a plain float32 reference of Phi-4-mini-flash-reasoning's
layer equations (microsoft/Phi-4-mini-flash-reasoning config.json,
model_type phi4flash; the decoder-hybrid-decoder of arXiv:2507.06607).

The equations (d 2560; 40 query heads over 20 kv heads x 64; MLP 10,240;
Mamba-1 inner 5120, state 16, 4 taps, dt_rank 160; window 512; LayerNorm
with weight and bias, eps 1e-5; no positional encoding; tied head). For N
layers, h = N // 2, layer i:

    x' = x + mixer_i(LN(x));  x'' = x' + fc2(silu(g) * u), [g | u] = LN(x') fc1
    even i <= h   Mamba-1:  [x | z] = y W_in;  x = silu(conv1d_causal(x))
                  [dt | B | C] = x W_x;  D_t = softplus(dt W_dt + dt_bias)
                  s_t[c,n] = exp(D_t[c] A[c,n]) s_{t-1}[c,n] + D_t[c] B_t[n] x_t[c]
                  m_t[c] = sum_n C_t[n] s_t[c,n] + D[c] x_t[c]
                  out = (m * silu(z)) W_out;  layer h hands m on
    odd  i <  h   differential attention, a query seeing itself and the
                  511 tokens before it:  [q | k | v] = y W_qkv + b_qkv
                  pair p: O_p = (softmax(q_2p k_2g^T / 8) - lambda softmax(
                  q_2p+1 k_2g+1^T / 8)) [v_2g | v_2g+1],  g = p // 2
                  lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init,
                  lambda_init = 0.8 - 0.6 exp(-0.3 i)
                  out = concat_p(rmsnorm_128(O_p; w) (1 - lambda_init)) W_o + b_o
    i == h + 1    the same over the whole sequence; hands its k, v on
    even i >  h   GMU:  out = (silu(y W_in) * m_h) W_out
    odd i > h + 1 cross:  q = y W_q + b_q over layer h + 1's k, v, the rest
                  as a differential-attention layer's own
    logits = LN(x_N) E^T;  loss = cross entropy against the next token

The reference runs the recurrence TOKEN BY TOKEN (`lax.scan` over t), both
softmax maps of a pair as dense masked matrices in blocks of queries
against all keys, no kernel, no cache, and no code shared with
ray_tpu.models. It says which layer is of which kind from N by the rule
above (`layer_kinds`), not from the weights. It reads the program's
parameter tree. The count functions take the program's config object or
the configuration file's dict and import no jax: per-layer readers call
them in run.py's parent process, which must never initialise a backend."""

from __future__ import annotations

import contextlib
import importlib.machinery
import importlib.util
import math

# A tree from before the family (the parent of the PR that brought it)
# says so as the cell is looked up, in run.py's own process, before a
# cluster or a chip is touched (families/granite_hybrid.py has the why).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.sambay", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models.sambay: "
                      "it cannot run a sambay configuration")

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three and ops/selective_scan.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_selective_fwd_kernel", "_selective_bwd_kernel")

_QUERY_BLOCK = 256
_LOSS_ROWS = 2048

MAMBA, WINDOWED, FULL, GMU, CROSS = "mamba", "windowed", "full", "gmu", "cross"


def layer_kinds(n_layers: int) -> tuple:
    """The model's own rule, from the depth alone."""
    h = n_layers // 2

    def kind(i):
        if i % 2 == 0:
            return MAMBA if i <= h else GMU
        if i < h:
            return WINDOWED
        return FULL if i == h + 1 else CROSS
    return tuple(kind(i) for i in range(n_layers))


def build(config: dict, **overrides):
    """The program's SambaYConfig at the file's sizes."""
    from ray_tpu.models.sambay import SambaYConfig

    for key, want in (("model_type", "phi4flash"), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False), ("mb_per_layer", 2),
                      ("embd_pdrop", 0), ("resid_pdrop", 0)):
        if config[key] != want:
            raise ValueError(f"models/sambay.py has {key} = {want!r} only, "
                             f"not {config[key]!r}")
    assumed = config["assumed"]
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_heads=config["num_attention_heads"],
              n_kv_heads=config["num_key_value_heads"],
              n_layers=config["num_hidden_layers"],
              d_ff=config["intermediate_size"],
              mb_per_layer=config["mb_per_layer"],
              sliding_window=config["sliding_window"],
              mamba_d_state=assumed["mamba_d_state"],
              mamba_d_conv=assumed["mamba_d_conv"],
              mamba_expand=assumed["mamba_expand"],
              mamba_dt_rank=assumed["mamba_dt_rank"],
              norm_eps=config["layer_norm_eps"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    return SambaYConfig(**kw)


# The cell's second limit, on what this family's kernels make: the
# largest of kernel_errors' relative errors. Two readings on the v5e at
# the published channel and head sizes (limit_readings.py; PERF.md
# section 4 has the tables): the program's kernels, and the same
# references with every input and value in bfloat16, the nearest
# precision below. Every structural fault below reads far above it.
KERNEL_LIMIT = 0.02


def hold_kernels(cfg):
    """Refuse a program whose scan, banded differential attention or
    gated memory unit is further from the float32 equations than
    KERNEL_LIMIT: the loss at initialisation, which drivers/train.py
    compares, sees little of any of them (PERF.md section 4), so the cell
    holds them to a limit of their own before it hands the program over."""
    from .. import harness

    errors = kernel_errors(cfg)
    worst = max(errors, key=errors.get)
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program is off the float32 equations by {errors[worst]:.3g} "
        f"of the largest value in {worst} (limit {KERNEL_LIMIT}): {errors}")


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test,
    its kernels held to KERNEL_LIMIT first where they are the chip's
    (elsewhere they are the jax.numpy forms, or the kernels interpreted
    in float32, and tier-1 holds both to the equations)."""
    import jax

    from ray_tpu.models.sambay import (make_sambay_train_step, sambay_init,
                                       sambay_loss)

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = make_sambay_train_step(cfg, mesh=mesh, rules=rules)
    return (lambda key: sambay_init(key, cfg), init_state, step,
            lambda params, batch: sambay_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _window_ignored(real):
    """Every windowed layer sees the whole sequence."""
    def flash_attention(q, k, v, causal=True, sm_scale=None, window=None):
        return real(q, k, v, causal, sm_scale, None)
    return flash_attention


def _lambda_term_dropped(real):
    """O_p = softmax(q_2p k_2g^T) V_g alone: the second map of every pair
    contributes nothing."""
    def flash_attention(q, k, v, causal=True, sm_scale=None, window=None):
        import jax.numpy as jnp
        maps = real(q, k, v, causal, sm_scale, window)
        second = (jnp.arange(maps.shape[1]) % 2 == 1)[None, :, None, None]
        return jnp.where(second, jnp.zeros_like(maps), maps)
    return flash_attention


def _chunk_carry_dropped(real):
    """Every chunk of 64 tokens starts from a zero state: chunks scanned
    as separate sequences, so only what crosses a boundary is lost."""
    def selective_scan(x, dt, A, B, C, D, initial_state=None):
        from ray_tpu.ops.selective_scan import CHUNK
        b, L = x.shape[:2]
        if L % CHUNK:
            return real(x, dt, A, B, C, D, initial_state)

        def cut(t):
            return t.reshape(b * (L // CHUNK), CHUNK, *t.shape[2:])
        m, final = real(cut(x), cut(dt), A, cut(B), cut(C), D)
        final = final.reshape(b, L // CHUNK, *final.shape[1:])[:, -1]
        return m.reshape(x.shape), final
    return selective_scan


def _gmu_memory_zeroed(real):
    """Every gated memory unit multiplies zeros: layer N/2's scan output
    never reaches the second half."""
    def gmu(x, layer, dec, m):
        import jax.numpy as jnp
        return real(x, layer, dec, jnp.zeros_like(m))
    return gmu


# What limit_readings.py plants in the program, one at a time, each named
# with the name models.decoder calls through. All four are faults of
# structure: KERNEL_LIMIT catches each on every seed (PERF.md section 4).
STRUCTURAL_FAULTS = {"window_ignored": ("flash_attention", _window_ignored),
                     "lambda_term_dropped": ("flash_attention",
                                             _lambda_term_dropped),
                     "chunk_carry_dropped": ("selective_scan",
                                             _chunk_carry_dropped),
                     "gmu_memory_zeroed": ("gmu", _gmu_memory_zeroed)}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault` in every layer it touches: models.decoder
    calls the scan, the flash kernel and the gated memory unit through its
    own names, which stand for the faulty ones meanwhile. Trace the
    program inside; a function jitted before keeps what it traced."""
    from ray_tpu.models import decoder

    name, make = STRUCTURAL_FAULTS[fault]
    real = getattr(decoder, name)
    setattr(decoder, name, make(real))
    try:
        yield
    finally:
        setattr(decoder, name, real)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's SambaYConfig or the configuration's dict."""
    if isinstance(cfg, dict):
        a = cfg["assumed"]
        s = dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                 v=cfg["vocab_size"], h=cfg["num_attention_heads"],
                 kv=cfg["num_key_value_heads"], w=cfg["sliding_window"],
                 L=cfg["num_hidden_layers"], N=a["mamba_d_state"],
                 K=a["mamba_d_conv"], R=a["mamba_dt_rank"],
                 inner=a["mamba_expand"] * cfg["hidden_size"])
    else:
        s = dict(d=cfg.d_model, f=cfg.d_ff, v=cfg.vocab_size, h=cfg.n_heads,
                 kv=cfg.n_kv_heads, w=cfg.sliding_window, L=cfg.n_layers,
                 N=cfg.mamba_d_state, K=cfg.mamba_d_conv, R=cfg.dt_rank,
                 inner=cfg.mamba_inner)
    s["hd"] = s["d"] // s["h"]
    kinds = layer_kinds(s["L"])
    s["count"] = {k: kinds.count(k) for k in (MAMBA, WINDOWED, FULL, GMU,
                                              CROSS)}
    return s


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs one score map needs: the causal triangle, or
    under a window the band (a query sees min(position + 1, window))."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


_SCAN_FLOPS = 6    # a (token, channel, state): dt A, the decay times the
#                    state, dt x B, their sum, C times the state, its sum


def forward_flops_per_token(cfg, seq: int) -> float:
    """Matmul, convolution and scan operations one token needs in the
    forward pass at context `seq`: every layer the fused SwiGLU MLP; a
    Mamba-1 layer its four projections, the convolution's taps and the
    scan's multiply-adds; an attention layer its projections and, a map,
    q k^T (64 wide) and p V (128 wide) over the pairs it sees (the band
    under a window); a GMU two projections; the tied head once."""
    s = _dims(cfg)
    d, inner, n = s["d"], s["inner"], s["count"]
    kv_d = s["kv"] * s["hd"]
    mlp = 3 * 2 * d * s["f"]
    mamba = (2 * d * 2 * inner + 2 * s["K"] * inner
             + 2 * inner * (s["R"] + 2 * s["N"]) + 2 * s["R"] * inner
             + _SCAN_FLOPS * inner * s["N"] + 2 * inner * d)

    def maps(window):
        return s["h"] * 2 * 3 * s["hd"] * visible_pairs(seq, window) / seq
    own = 2 * d * (d + 2 * kv_d) + 2 * d * d
    return (s["L"] * mlp + n[MAMBA] * mamba
            + n[WINDOWED] * (own + maps(s["w"])) + n[FULL] * (own + maps(None))
            + n[CROSS] * (2 * 2 * d * d + maps(None))
            + n[GMU] * 2 * 2 * d * inner + 2 * d * s["v"])


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation (remat,
    the kernels' tiles and chunks made again in their backward) is not
    counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step:
    a map forward q k^T and p V, backward dV, dP, dQ, dK, the first and
    the last two 64 wide and the others 128, over the pairs the map sees:
    the BAND's for the windowed layers, so kernels that mask instead of
    skipping read low."""
    s = _dims(cfg)
    n = s["count"]
    pairs = (n[WINDOWED] * visible_pairs(seq, s["w"])
             + (n[FULL] + n[CROSS]) * visible_pairs(seq))
    return batch * s["h"] * 2 * 9 * s["hd"] * pairs


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv; o twice
    as wide as q, k and v counted at their 20 heads, not their copies
    across a group and a pair. bf16."""
    s = _dims(cfg)
    n = s["count"]
    q = batch * seq * s["d"] * 2
    kv = batch * seq * s["kv"] * s["hd"] * 2
    return (n[WINDOWED] + n[FULL] + n[CROSS]) * (3 * q + 3 * 2 * q + 6 * kv)


def selective_scan_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the scan kernels in one train step, the
    Mamba-1 layers only: six multiply-adds a (token, channel, state)
    forward and twice that for their gradients. Exponentials are not
    operations a roofline counts."""
    s = _dims(cfg)
    return (s["count"][MAMBA] * 3.0 * _SCAN_FLOPS * batch * seq * s["inner"]
            * s["N"])


def selective_scan_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: the forward reads x, dt, B, C
    and writes m; the backward reads x, dt, B, C, dm and writes dx, d dt,
    dB, dC. x, m, B, C and their gradients bf16, dt and its gradient
    float32. The state kept a chunk, the float32 copies the kernels take
    at their boundary and dB's and dC's lanes are left out: fewer bytes,
    never more."""
    s = _dims(cfg)
    token = (s["inner"] * (2 + 4 + 2) + 2 * s["N"] * 2              # forward
             + s["inner"] * (2 + 4 + 2 + 2 + 4) + 4 * s["N"] * 2)   # backward
    return s["count"][MAMBA] * batch * seq * token


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * w + b).astype(x.dtype)


def _silu(x):
    import jax.numpy as jnp
    return x / (1.0 + jnp.exp(-x))


def _blocks(n: int, limit: int) -> int:
    """The largest block size up to `limit` that divides n."""
    return max(b for b in range(1, min(n, limit) + 1) if n % b == 0)


def recurrence(x, delta, A, B, C, skip, initial_state=None):
    """The selective scan as it is defined, one token after another:
    s_t[c, n] = exp(delta_t[c] A[c, n]) s_{t-1}[c, n] + delta_t[c] B_t[n]
    x_t[c];  m_t[c] = sum_n C_t[n] s_t[c, n] + skip[c] x_t[c]. x, delta
    [b, S, c]; A [c, N]; B, C [b, S, N]; skip [c]. Returns (m [b, S, c],
    the final state [b, c, N]), in x's dtype."""
    import jax
    import jax.numpy as jnp

    dtype = x.dtype
    b, _, c = x.shape

    def step(s, t):
        x_t, d_t, B_t, C_t = t
        s = (jnp.exp(d_t[..., None] * A) * s
             + (d_t * x_t)[..., None] * B_t[:, None, :]).astype(dtype)
        m_t = jnp.einsum("bcn,bn->bc", s, C_t) + skip * x_t
        return s, m_t.astype(dtype)

    if initial_state is None:
        initial_state = jnp.zeros((b, c, A.shape[1]), dtype)
    s, ms = jax.lax.scan(step, initial_state, tuple(
        t.swapaxes(0, 1) for t in (x, delta, B, C)))
    return ms.swapaxes(0, 1), s


def differential_attention(q, k, v, lam, lam_init, weight, eps,
                           window=None):
    """q [b, S, h, hd]; k, v [b, S, kvh, hd]; -> [b, S, h hd]. Pair p of
    query heads over kv pair p // group: two dense masked softmax maps,
    their difference times both heads' values side by side, one RMSNorm
    over the 2 hd columns, times 1 - lambda_init. Query blocks against
    all keys."""
    import jax
    import jax.numpy as jnp

    b, S, h, hd = q.shape
    kvh = k.shape[2]
    pairs, group = h // 2, h // kvh
    q = q.reshape(b, S, pairs // group, group, 2, hd)
    k = k.reshape(b, S, kvh // 2, 2, hd)
    v = v.reshape(b, S, kvh // 2, 2 * hd)
    block = _blocks(S, _QUERY_BLOCK)
    key_pos = jnp.arange(S)

    def one_block(args):
        qb, first = args                # [b, block, kv pairs, group, 2, hd]
        sc = jnp.einsum("bqgpeh,bkgeh->bgpeqk", qb, k) / math.sqrt(hd)
        at = (first + jnp.arange(block))[:, None]
        seen = key_pos[None, :] <= at
        if window is not None:
            seen &= key_pos[None, :] > at - window
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1).astype(v.dtype)
        diff = p[:, :, :, 0] - lam.astype(v.dtype) * p[:, :, :, 1]
        return jnp.einsum("bgpqk,bkgd->bqgpd", diff, v)

    out = jax.lax.map(one_block, (
        q.reshape(b, S // block, block, *q.shape[2:]).swapaxes(0, 1),
        jnp.arange(0, S, block)))
    out = out.swapaxes(0, 1).reshape(b, S, pairs, 2 * hd)
    out = out * (1.0 / jnp.sqrt(jnp.mean(out * out, -1, keepdims=True) + eps))
    return ((out * weight * (1.0 - lam_init)).astype(v.dtype)
            .reshape(b, S, h * hd))


def _lambda(lay, index: int):
    """(lambda, lambda_init) of layer `index`."""
    import jax.numpy as jnp
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    return (jnp.exp(jnp.sum(lay["lambda_q1"] * lay["lambda_k1"]))
            - jnp.exp(jnp.sum(lay["lambda_q2"] * lay["lambda_k2"]))
            + lam_init), lam_init


def _attention(y, lay, cfg, index: int, window, handed=None):
    """y [b, S, d] -> (out, (k, v)): over its own keys and values, or
    (`handed`) another layer's."""
    import jax.numpy as jnp

    b, S, d = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if handed is None:
        q, k, v = jnp.split(y @ lay["wqkv"] + lay["bqkv"],
                            [h * hd, (h + kvh) * hd], axis=-1)
        k, v = k.reshape(b, S, kvh, hd), v.reshape(b, S, kvh, hd)
    else:
        q, (k, v) = y @ lay["wq"] + lay["bq"], handed
    lam, lam_init = _lambda(lay, index)
    out = differential_attention(q.reshape(b, S, h, hd), k, v, lam, lam_init,
                                 lay["sub_norm"], cfg.norm_eps, window)
    return out @ lay["wo"] + lay["bo"], (k, v)


def _mamba1(y, lay, cfg):
    """y [b, S, d] -> (out [b, S, d], m [b, S, inner], the final state)."""
    import jax.numpy as jnp

    s = y.shape[1]
    N, taps, rank = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank
    x, z = jnp.split(y @ lay["in_proj"], 2, axis=-1)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = _silu(lay["conv_b"] + sum(
        padded[:, k:k + s] * lay["conv_w"][:, k] for k in range(taps)))
    dt, B, C = jnp.split(x @ lay["x_proj"], [rank, rank + N], axis=-1)
    # softplus, the decay and the state in the working precision (float32
    # unless the all-bfloat16 reading asks otherwise)
    dtype = y.dtype
    delta = jnp.logaddexp(dt @ lay["dt_proj"] + lay["dt_bias"], 0.0
                          ).astype(dtype)
    m, state = recurrence(x, delta, -jnp.exp(lay["A_log"]).astype(dtype),
                          B, C, lay["D"].astype(dtype))
    return (m * _silu(z)) @ lay["out_proj"], m, state


def _hidden(params, tokens, cfg, dtype=None):
    """(final-norm rows [b, s, d], E [V, d], the Mamba-1 layers' final
    states), every parameter and so every value in `dtype` (float32 unless
    given)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    x = p["embed"][tokens]
    eps = cfg.norm_eps
    kinds = layer_kinds(len(p["layers"]))
    m = handed = None
    states = []
    for i, (kind, lay) in enumerate(zip(kinds, p["layers"])):
        y = _layer_norm(x, lay["ln1"], lay["ln1_b"], eps)
        if kind == MAMBA:
            out, m, state = _mamba1(y, lay, cfg)
            states.append(state)
        elif kind == GMU:
            out = (_silu(y @ lay["gmu_in"]) * m) @ lay["gmu_out"]
        elif kind == CROSS:
            out, _ = _attention(y, lay, cfg, i, None, handed)
        else:
            out, kv = _attention(y, lay, cfg, i,
                                 cfg.sliding_window if kind == WINDOWED
                                 else None)
            if kind == FULL:
                handed = kv
        x = x + out
        y = _layer_norm(x, lay["ln2"], lay["ln2_b"], eps)
        gate, up = jnp.split(y @ lay["fc1"], 2, axis=-1)
        x = (x + (_silu(gate) * up) @ lay["fc2"]).astype(p["embed"].dtype)
    return (_layer_norm(x, p["lnf"], p["lnf_b"], eps), p["embed"], states)


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    x, embed, _ = _hidden(params, tokens, cfg)
    return x @ embed.T


def reference_final_states(params, tokens, cfg):
    """The state [b, inner, N] each Mamba-1 layer is left in, in layer
    order, float32."""
    return _hidden(params, tokens, cfg)[2]


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """Mean next-token cross entropy, in float32, the logits a block of
    rows at a time. `dtype` is for setting the comparison's limit only:
    the same reference with every parameter and value in a lower
    precision (bfloat16) has to come out as not correct (PERF.md)."""
    import jax
    import jax.numpy as jnp

    x, embed, _ = _hidden(params, tokens, cfg, dtype)
    rows = x.reshape(-1, x.shape[-1])
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax((xb @ embed.T).astype(jnp.float32), -1)
        return jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1))

    total = jax.lax.map(one_block, (rows.reshape(-1, block, rows.shape[-1]),
                                    targets.reshape(-1, block)))
    return -jnp.sum(total) / targets.size


# ---------------------------------------------------------------------------
# the kernels against the equations, at the published sizes
# ---------------------------------------------------------------------------
def kernel_errors(cfg, seed: int = 0, low: bool = False) -> dict:
    """What this family's kernels make, as models.decoder calls them (on a
    TPU the Mosaic kernels), against the equations above in float32, at
    the configuration's channel and head sizes and four windows of tokens
    (2,048 at the published 512); for each
    value the largest |got - want| over the largest |want|:

      scan_*   the selective scan from a seeded initial state, inputs as a
               Mamba-1 layer makes them at initialisation: m, the final
               state, and the gradient of a seeded weighted sum of both by
               each of x, dt, A, B, C, D and the initial state
      attn_*   differential attention under the window, between the
               projections (seeded q, k, v, lambdas and sub-norm weight):
               the output and the gradient of a seeded weighted sum of it
               by q, k and v
      gmu_out  a gated memory unit's output on a seeded x and m

    With `low`, what is compared is the equations themselves with every
    input and value in bfloat16: the second reading KERNEL_LIMIT lies
    under."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    f32 = jnp.float32
    inner, N = cfg.mamba_inner, cfg.mamba_d_state
    h, kvh, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    window, index = cfg.sliding_window, 1
    b, S = 1, max(256, min(2048, 4 * window))
    normal = jax.random.normal
    dec = cfg.decoder()

    @jax.jit
    def seeded(key):
        ks = jax.random.split(key, 20)
        step0 = jnp.exp(jax.random.uniform(
            ks[0], (inner,), minval=math.log(1e-3), maxval=math.log(1e-1)))
        scan = (
            (0.5 * normal(ks[1], (b, S, inner))).astype(cfg.dtype),      # x
            jax.nn.softplus(0.5 * normal(ks[2], (b, S, inner))
                            + step0 + jnp.log(-jnp.expm1(-step0))),     # dt
            -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=f32), (inner, N)),
            normal(ks[4], (b, S, N)).astype(cfg.dtype),                 # B
            normal(ks[5], (b, S, N)).astype(cfg.dtype),                 # C
            jnp.ones((inner,), f32),                                    # D
            normal(ks[6], (b, inner, N)))                   # initial state
        attn = (normal(ks[7], (b, h, S, hd)).astype(cfg.dtype),
                normal(ks[8], (b, kvh, S, hd)).astype(cfg.dtype),
                normal(ks[9], (b, kvh, S, hd)).astype(cfg.dtype))
        lay = {"lambda_q1": 0.1 * normal(ks[10], (hd,)),
               "lambda_k1": 0.1 * normal(ks[11], (hd,)),
               "lambda_q2": 0.1 * normal(ks[12], (hd,)),
               "lambda_k2": 0.1 * normal(ks[13], (hd,)),
               "sub_norm": 1.0 + 0.1 * normal(ks[14], (2 * hd,)),
               "ln1": jnp.ones((d,), f32), "ln1_b": jnp.zeros((d,), f32),
               "gmu_in": (normal(ks[15], (d, inner)) * d ** -0.5
                          ).astype(cfg.dtype),
               "gmu_out": (normal(ks[16], (inner, d)) * inner ** -0.5
                           ).astype(cfg.dtype)}
        x = normal(ks[17], (b, S, d)).astype(cfg.dtype)
        weights = (normal(ks[18], (b, S, inner)), normal(ks[19], (b, inner, N)),
                   normal(ks[3], (b, S, d)))
        return scan, attn, lay, x, weights

    scan_in, attn_in, lay, x, (wm, ws, wo) = seeded(jax.random.PRNGKey(seed))
    m_in = scan_in[0]

    def cast(tree, dtype):
        return jax.tree.map(lambda t: t.astype(dtype), tree)

    def scan_program(*given):
        return decoder.selective_scan(*given)

    def scan_equations(dtype):
        def fn(*given):
            x_, dt, A, B, C, D, init = cast(given, dtype)
            return recurrence(x_, dt, A, B, C, D, init)
        return fn

    def attn_program(q, k, v):
        return decoder.differential_maps(q, k, v, lay, dec, index, window)

    def attn_equations(dtype):
        def fn(q, k, v):
            q, k, v = (t.swapaxes(1, 2) for t in cast((q, k, v), dtype))
            lam, lam_init = _lambda(lay, index)
            return differential_attention(
                q, k, v, lam, lam_init, lay["sub_norm"].astype(dtype),
                cfg.norm_eps, window)
        return fn

    def gmu_program(x_, m_):
        return decoder.gmu(x_, lay, dec, m_)

    def gmu_equations(dtype):
        def fn(x_, m_):
            x_, m_, w_in, w_out = cast(
                (x_, m_, lay["gmu_in"], lay["gmu_out"]), dtype)
            y = _layer_norm(x_, lay["ln1"].astype(dtype),
                            lay["ln1_b"].astype(dtype), cfg.norm_eps)
            return (_silu(y @ w_in) * m_) @ w_out
        return fn

    def scan_all(fn):
        def scalar(*given):
            m, state = fn(*given)
            return (jnp.sum(m.astype(f32) * wm)
                    + jnp.sum(state.astype(f32) * ws)), (m, state)

        def run(*given):
            (_, (m, state)), grads = jax.value_and_grad(
                scalar, argnums=tuple(range(7)), has_aux=True)(*given)
            return (m, state, *grads)
        return jax.jit(run)

    def attn_all(fn):
        def scalar(*given):
            out = fn(*given)
            return jnp.sum(out.astype(f32) * wo), out

        def run(*given):
            (_, out), grads = jax.value_and_grad(
                scalar, argnums=(0, 1, 2), has_aux=True)(*given)
            return (out, *grads)
        return jax.jit(run)

    def everything(scan, attn, gmu_fn):
        return (*scan_all(scan)(*scan_in), *attn_all(attn)(*attn_in),
                jax.jit(gmu_fn)(x, m_in))

    with jax.default_matmul_precision("highest"):
        want = everything(scan_equations(f32), attn_equations(f32),
                          gmu_equations(f32))
        if low:
            bf16 = jnp.bfloat16
            got = everything(scan_equations(bf16), attn_equations(bf16),
                             gmu_equations(bf16))
    if not low:       # as it runs: no precision asked
        got = everything(scan_program, attn_program, gmu_program)
    errors = jax.jit(lambda got, want: [
        jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
        / jnp.max(jnp.abs(w.astype(f32))) for g, w in zip(got, want)])
    names = ("scan_m", "scan_state", "scan_dx", "scan_ddt", "scan_dA",
             "scan_dB", "scan_dC", "scan_dD", "scan_dinit",
             "attn_out", "attn_dq", "attn_dk", "attn_dv", "gmu_out")
    return dict(zip(names, map(float, errors(got, want)), strict=True))
