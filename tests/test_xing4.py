"""models.xing4 (Xing4.0-29B-A4B: several residual streams joined by
manifold-constrained hyper-connections round latent attention with a
low-rank query path and YaRN, a dense SwiGLU layer and then a held share
of SwiGLU experts behind a sigmoid router with a selection bias, beside a
gated shared expert, an untied head) against the benchmark's plain float32
reference (chipbench/families/xing4.py) on seeded weights, and the pieces
this family brought to models/decoder.py, ops/layers.py and
parallel/moe.py: the residual rule that is not an add, the thirteenth kind
of layer with its latent cache, what its block keeps under remat, YaRN's
frequencies, the gated form of the held layer's shared branch."""

import dataclasses
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import xing4 as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.xing4 import (HC_BRANCHES, Xing4Config,
                                  make_xing4_train_step, xing4_forward,
                                  xing4_init, xing4_loss, xing4_param_axes)
from ray_tpu.ops import attention
from ray_tpu.ops.layers import yarn_inv_freq, yarn_mscale
from ray_tpu.parallel.moe import held_moe_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "chipbench/configs/xing4.0-29b-a4b.json"
# float32 program against float32 reference: the same sums in another
# order (a latent and one product more against per-head keys and values;
# four streams apart against one value; sorted rows and grouped products
# against every expert on every token).
TOL = 1e-4
# bfloat16 program against the float32 reference on a loss of 5.5: a
# rounding is 2^-8 and a gradient is some ten of them deep. The limits that
# separate the precisions are the cell's, read on the chip.
TOL_BF16_LOSS = 4e-3
TOL_BF16 = 8e-2


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tiny(dtype=jnp.float32, **changes):
    return dataclasses.replace(Xing4Config.tiny(), dtype=dtype, **changes)


# Sinkhorn rounds where their number is not what a test is about: the
# rounds are unrolled, and twenty of them a branch are most of a tiny
# model's program.
FEW = 4
# The whole-model cases' size: a dense and an expert layer, few rounds of
# the hyper-connections' and of the bias's rule, the biases from zero (the
# train-step case keeps the balanced start).
CHEAP = dict(n_layers=2, hc_sinkhorn_iters=FEW, bias_rounds=8,
             balance_tokens=0)


def _spread(params, cfg, key=7):
    """`params` with every hyper-connection drawn so that its three sets of
    coefficients spread by O(1) across tokens (at the start's gains of
    0.01 the dynamic part is invisible): phi at 1 / sqrt(n d), the gains
    0.5 to 1.5, the static parts at 0.5, 2 on H_res's diagonal."""
    n, d = cfg.hc_mult, cfg.d_model
    layers = []
    for i, layer in enumerate(params["layers"]):
        layer = dict(layer)
        for j, branch in enumerate(HC_BRANCHES):
            k = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(key), 2 * i + j), 3)
            layer[branch] = {
                "phi": (jax.random.normal(k[0], (n * d, 2 * n + n * n))
                        * (n * d) ** -0.5).astype(cfg.dtype),
                "alpha": jax.random.uniform(k[1], (3,), minval=0.5,
                                            maxval=1.5),
                "b": jnp.concatenate([jnp.zeros(2 * n),
                                      2.0 * jnp.eye(n).reshape(-1)])
                + 0.5 * jax.random.normal(k[2], (2 * n + n * n,))}
        layers.append(layer)
    return {**params, "layers": layers}


@pytest.fixture(scope="module")
def tiny():
    """(config in float32, the dense layer and one expert layer of it, its
    seeded weights with the balanced biases and the hyper-connections drawn
    apart, a batch of two 40-token sequences)."""
    cfg = _tiny(**CHEAP)
    params = _spread(xing4_init(jax.random.PRNGKey(0), cfg), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                             cfg.vocab_size)
    return cfg, params, (tok, jnp.roll(tok, -1, 1))


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------
def _loss_and_gradients(loss, params, batch):
    return jax.jit(jax.value_and_grad(lambda p: loss(p, batch)))(params)


def test_loss_and_every_gradient_are_the_references(tiny):
    cfg, params, batch = tiny
    want, dwant = _loss_and_gradients(
        lambda p, b: reference.reference_loss(p, b[0], b[1], cfg), params,
        batch)
    got, dgot = _loss_and_gradients(
        lambda p, b: xing4_loss(p, b, cfg), params, batch)
    assert abs(float(got) - float(want)) <= TOL * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(dwant))
    for path, g in jax.tree_util.tree_leaves_with_path(dgot):
        if "router_bias" in jax.tree_util.keystr(path):
            continue                        # no gradient reaches it
        w = flat_want[path]
        assert float(jnp.max(jnp.abs(w))) > 0, path
        _close(g, w)
    # the coefficients were drawn apart: the streams mix by a tenth or more
    # and no two layers' hyper-connections are alike
    assert float(jnp.max(jnp.abs(dwant["layers"][1]["hc_mlp"]["phi"]))) > 0


def test_bfloat16_program_is_near_the_float32_reference(tiny):
    cfg32, params32, batch = tiny
    cfg = dataclasses.replace(cfg32, dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2
        and a.shape[-1] != cfg.n_experts else a, params32)
    want, dwant = _loss_and_gradients(
        lambda p, b: reference.reference_loss(p, b[0], b[1], cfg), params,
        batch)
    got, dgot = _loss_and_gradients(
        lambda p, b: xing4_loss(p, b, cfg), params, batch)
    assert abs(float(got) - float(want)) <= TOL_BF16_LOSS * float(want)
    for name in ("embed", "head"):
        _close(dgot[name], dwant[name], TOL_BF16)
    for got_layer, want_layer in zip(dgot["layers"], dwant["layers"]):
        for name in ("w_qa", "w_kvb", "wo"):
            _close(got_layer[name], want_layer[name], TOL_BF16)
        for branch in HC_BRANCHES:
            _close(got_layer[branch]["phi"], want_layer[branch]["phi"],
                   TOL_BF16)


@pytest.fixture(scope="module")
def full_forwards(tiny):
    """(the program's training forward, the reference's) of `tiny`'s batch,
    once for both cases below."""
    cfg, params, (tok, _) = tiny
    with jax.default_matmul_precision("highest"):
        return (jax.jit(lambda p, t: xing4_forward(p, t, cfg))(params, tok),
                jax.jit(lambda p, t: reference.reference_logits(p, t, cfg))(
                    params, tok))


@pytest.mark.parametrize("positions", ["scalar", "a_row"])
def test_prefill_then_decode_is_the_references_full_forward(
        tiny, full_forwards, positions):
    """Through the latent cache: a prefill of 33 tokens and 7 single steps
    give the logits of the training forward and of the reference, with the
    start position a scalar or one a row of the batch; the streams are no
    state."""
    cfg, params, (tok, _) = tiny
    cache = init_cache(cfg, 2, 48)
    assert [sorted(layer) for layer in cache] == [["k_rope", "latent"]] * 2
    assert cache[0]["latent"].shape == (2, 48, cfg.kv_lora_rank)
    assert cache[0]["k_rope"].shape == (2, 48, cfg.qk_rope_head_dim)

    def at(t):
        return jnp.int32(t) if positions == "scalar" else jnp.array([t, t])

    # two programs, the prefill's and a single token's
    forward = jax.jit(lambda tokens, cache, start: cached_forward(
        params, tokens, cache, start, cfg))
    logits, cache = forward(tok[:, :33], cache, at(0))
    steps = [logits]
    for t in range(33, 40):
        logits, cache = forward(tok[:, t:t + 1], cache, at(t))
        steps.append(logits)
    got = jnp.concatenate(steps, axis=1)
    full, want = full_forwards
    _close(got, full)
    _close(got, want)
    assert float(jnp.max(jnp.abs(cache[0]["latent"][:, 40:]))) == 0.0
    if positions == "a_row":
        # continuous batching's case: row 1 is set back to 12
        step = jnp.stack([tok[0, 39:40], tok[1, 12:13]])
        logits, _ = forward(step, cache, jnp.array([39, 12]))
        _close(logits[0, 0], full[0, 39])
        _close(logits[1, 0], full[1, 12])


def test_the_latent_cache_holds_576_values_a_token_at_the_published_sizes():
    """The cell's configuration (and the published model: the widths are
    the same): a layer's state is the normed latent and the rotated shared
    key, 512 + 64 values a token, 1,152 bytes in bfloat16 where per-head
    keys and values would be 32 x (192 + 128) x 2 = 20,480."""
    with open(os.path.join(ROOT, CONFIG)) as f:
        cfg = reference.build(json.load(f), balance_tokens=0)
    cache = init_cache(cfg, 2, 256)
    assert len(cache) == 5
    for layer in cache:
        assert {k: (v.shape, v.dtype) for k, v in layer.items()} == {
            "latent": ((2, 256, 512), jnp.bfloat16),
            "k_rope": ((2, 256, 64), jnp.bfloat16)}
    a_token = sum(v.size * v.dtype.itemsize
                  for v in cache[0].values()) // (2 * 256)
    assert a_token == 1152
    full = Xing4Config.xing4_29b_a4b()
    assert dataclasses.replace(
        full, n_layers=5, n_dense_layers=1, experts_held=(0, 8),
        vocab_size=16384, balance_tokens=0) == cfg
    assert full.n_heads * (full.qk_head_dim + full.v_head_dim) * 2 == 20480


def test_train_step_keeps_the_biases_apart_and_carries_the_counters():
    cfg = _tiny(n_layers=2, hc_sinkhorn_iters=FEW)
    init_state, step = make_xing4_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    assert state["held"].shape == (1, cfg.n_experts)
    assert all("router_bias" not in layer
               for layer in state["params"]["layers"])
    axes = xing4_param_axes(cfg)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(
            lambda a: 0, xing4_init(jax.random.PRNGKey(0), cfg)))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                             cfg.vocab_size)
    losses = []
    for _ in range(3):
        state, metrics = step(state, (tok, jnp.roll(tok, -1, 1)))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert metrics["expert_tokens"].shape == (1, cfg.n_experts)
    assert metrics["expert_rows_held"].shape == (1,)
    # a row a layer, its attention branch then its feed-forward one
    assert metrics["hc_res_offdiag_max"].shape == (2, 2)
    assert metrics["hc_res_col_err_max"].shape == (2, 2)
    # at the start H_res is within 1e-3 of the identity, rounds or none
    assert float(jnp.max(metrics["hc_res_col_err_max"])) < 1e-3
    assert 0 < float(jnp.max(metrics["hc_res_offdiag_max"])) < 1e-2
    np.testing.assert_array_equal(state["held"], metrics["router_bias"])


# ---------------------------------------------------------------------------
# the residual rule that is not an add
# ---------------------------------------------------------------------------
def _streams_and_weights(n=4, d=32, rows=48, logits=1.0):
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    streams = tuple(jax.random.normal(kk, (2, rows, d))
                    for kk in jax.random.split(k[0], n))
    hc = {"phi": jax.random.normal(k[1], (n * d, 2 * n + n * n))
          * (n * d) ** -0.5,
          "alpha": logits * jax.random.uniform(k[2], (3,), minval=0.5,
                                               maxval=1.5),
          "b": logits * jax.random.normal(k[3], (2 * n + n * n,))}
    return streams, hc


def test_h_res_is_doubly_stochastic_and_the_clamp_holds_it_finite():
    hyper = decoder.HyperConnections()
    assert hyper == (4, 20, 1e-6, (-30.0, 30.0))
    streams, hc = _streams_and_weights()
    h_pre, h_post, h_res = decoder.hyper_connection(streams, hc, hyper)
    assert h_pre.shape == h_post.shape == (2, 48, 4)
    assert h_res.shape == (2, 48, 4, 4)
    assert float(jnp.min(h_res)) >= 0
    assert float(jnp.max(h_res)) - float(jnp.min(h_res)) > 0.5   # O(1) logits
    assert float(jnp.max(jnp.abs(jnp.sum(h_res, -1) - 1))) < 1e-5    # rows
    assert float(jnp.max(jnp.abs(jnp.sum(h_res, -2) - 1))) < 1e-3    # columns
    assert 0 < float(jnp.min(h_pre)) and float(jnp.max(h_pre)) < 1
    assert 0 < float(jnp.min(h_post)) and float(jnp.max(h_post)) < 2
    # and the reference's, on one [b, s, n, d] value
    want = reference._hyper(jnp.stack(streams, 2), hc,
                            reference._Hyper(*hyper))
    for g, w in zip((h_pre, h_post, h_res), want):
        _close(g, w, 1e-5)
    # logits far past +-30: exp alone would overflow float32 at 89
    streams, hc = _streams_and_weights(logits=1e3)
    far = decoder.hyper_connection(streams, hc, hyper)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in far)
    assert float(jnp.max(jnp.abs(jnp.sum(far[2], -1) - 1))) < 1e-5
    loose = decoder.hyper_connection(
        streams, hc, hyper._replace(clamp=(-1e4, 1e4)))
    assert not bool(jnp.all(jnp.isfinite(loose[2])))


def test_a_hyper_connected_branch_is_the_reference_with_every_gradient():
    """`_streams_read` round a fixed linear branch: the streams it returns
    and their gradient by the streams, phi, b and alpha; and what it
    counts."""
    hyper = decoder.HyperConnections(sinkhorn_iters=8)
    streams, hc = _streams_and_weights()
    F = jax.random.normal(jax.random.PRNGKey(6), (32, 32)) * 32 ** -0.5
    w = jax.random.normal(jax.random.PRNGKey(8), (2, 48, 4, 32))

    def program(X, hc):
        u, write, counted = decoder._streams_read(
            tuple(X[:, :, i] for i in range(4)), hc, hyper)
        return jnp.stack(write(u @ F), 2), counted

    def plain(X, hc):
        return reference._joined(X, hc, reference._Hyper(*hyper),
                                 lambda u: u @ F)

    X = jnp.stack(streams, 2)
    want, dwant = jax.jit(jax.value_and_grad(
        lambda X, hc: jnp.sum(plain(X, hc) * w), argnums=(0, 1)))(X, hc)
    got, dgot = jax.jit(jax.value_and_grad(
        lambda X, hc: jnp.sum(program(X, hc)[0] * w), argnums=(0, 1)))(X, hc)
    _close(got, want)
    for g, w_ in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        assert float(jnp.max(jnp.abs(w_))) > 0
        _close(g, w_)
    h_res = reference._hyper(X, hc, reference._Hyper(*hyper))[2]
    off_diagonal, column_error = jax.jit(program)(X, hc)[1]
    _close(off_diagonal, jnp.max(h_res * (1 - jnp.eye(4))), 1e-5)
    assert 0.3 < float(off_diagonal) < 1
    assert float(column_error) < 1e-2       # eight rounds, not twenty


def test_the_start_is_a_pre_norm_block_on_the_streams_sum():
    """The program's start: H_pre = 1/2, H_post = 1 and H_res within 1e-3
    of the identity, whatever the tokens."""
    cfg = _tiny(balance_tokens=0)
    hc = xing4_init(jax.random.PRNGKey(0), cfg)["layers"][0]["hc_mixer"]
    n, d = cfg.hc_mult, cfg.d_model
    assert hc["phi"].shape == (n * d, 2 * n + n * n)
    streams = tuple(jax.random.normal(jax.random.PRNGKey(i), (1, 16, d))
                    for i in range(n))
    h_pre, h_post, h_res = decoder.hyper_connection(
        streams, hc, cfg.decoder().hyper)
    assert float(jnp.max(jnp.abs(h_pre - 0.5))) < 0.05
    assert float(jnp.max(jnp.abs(h_post - 1.0))) < 0.1
    assert float(jnp.max(jnp.abs(h_res - jnp.eye(n)))) < 2e-3


def _parents_block(x, layer, cache, start_pos, shared=decoder.Shared(), *,
                   dec, kind, mlp=None, index=0, window=None):
    """`decoder._block` as the parent of PR 53 had it: both branches joined
    by the add."""
    eps, row = dec.norm_eps, decoder.MIXERS[kind]
    stats, new_cache = None, cache
    if row.apply is not None:
        with jax.named_scope(decoder.MIXER_SCOPES[kind]):
            y, new_cache, shared = row.apply(
                x, layer, dec, cache, start_pos, shared, index, window)
            x = x + decoder._scaled(
                decoder._norm_if_held(y, layer, "post_attention", eps),
                dec.residual_scale)
    if row.channel:
        with jax.named_scope("channel_mixer"):
            out, stats = mlp(decoder._norm_if_held(x, layer, "ln2", eps),
                             layer)
            out = decoder._norm_if_held(out, layer, "post_feedforward", eps)
            x = x + decoder._scaled(out, dec.residual_scale)
    return x, stats, new_cache, shared


def test_a_layer_that_holds_no_hyper_connection_lowers_as_before():
    """What a layer holds says what its block does: Llama's block, which
    holds neither `hc_mixer` nor `hc_mlp`, is traced and lowered to the
    text the parent's `_block` gives, the scopes with it."""
    cfg = LlamaConfig.tiny()
    dec = cfg.decoder()
    layer = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"][0]
    x = jax.ShapeDtypeStruct((2, 128, cfg.d_model), cfg.dtype)
    assert dec.hyper is None and not set(HC_BRANCHES) & set(layer)

    def lowered(block):
        fn = functools.partial(block, dec=dec, kind=dec.kinds[0],
                               mlp=dec.mlp[0])
        return jax.jit(lambda x, layer: fn(x, layer, None, None)[0]).lower(
            x, layer).as_text(debug_info=True)

    ours, parents = lowered(decoder._block), lowered(_parents_block)
    def strip(text):        # source locations are all that may differ
        lines = (re.sub(r"\s*loc\(.*\)$", "", line)
                 for line in text.splitlines() if not line.startswith("#loc"))
        return [line for line in lines if line.strip()]

    assert "hc_" not in ours and len(strip(ours)) > 50
    assert strip(ours) == strip(parents)


# ---------------------------------------------------------------------------
# the thirteenth kind of layer
# ---------------------------------------------------------------------------
def _latent_layer(cfg, key=0):
    """A latent-attention layer's weights at 1 / sqrt(fan-in), the norms
    off one."""
    from ray_tpu.models.xing4 import _attention_init
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    lay = _attention_init(ks[0], cfg)
    lay = {name: w if w.ndim == 1 else w * (
        w.shape[0] ** -0.5 / cfg.init_std) for name, w in lay.items()}
    for i, (name, width) in enumerate((("ln1", cfg.d_model),
                                       ("q_latent_norm", cfg.q_lora_rank),
                                       ("latent_norm", cfg.kv_lora_rank))):
        lay[name] = 1 + 0.1 * jax.random.normal(ks[i + 1], (width,))
    return lay


def _plain_layer(x, lay, cfg, fault=None):
    y = reference._rms_norm(x, lay["ln1"], cfg.norm_eps)
    return reference._mla(y, lay, reference._sizes_of(cfg), fault)


@pytest.mark.parametrize("seq", [40, 128])
def test_a_latent_layer_is_the_reference_with_every_gradient(monkeypatch,
                                                             seq):
    """From the input norm to W_o: the output and its gradient by the rows
    and all six weights, in the jax.numpy branch (40 rows) and through
    the flash kernels interpreted at q and k wider than v (128 rows)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = _tiny()
    lay = _latent_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, cfg.d_model))
    wy = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def every(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, lay: jnp.sum(fn(x, lay) * wy), argnums=(0, 1)))(x, lay)

    want, dwant = every(lambda x, lay: _plain_layer(x, lay, cfg))
    got, dgot = every(lambda x, lay: decoder.latent_attention(
        x, lay, cfg.decoder())[0])
    _close(got, want)
    for g, w in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        assert float(jnp.max(jnp.abs(w))) > 0
        _close(g, w)
    if seq == 40:       # and each planted fault of the layer is another layer
        for fault in ("rope_on_the_no_rope_columns", "latent_norm_left_out",
                      "query_latent_norm_left_out", "frequencies_not_scaled",
                      "scale_without_mscale"):
            off = jax.jit(functools.partial(_plain_layer, cfg=cfg,
                                            fault=fault))(x, lay)
            assert float(jnp.max(jnp.abs(off - want))) > 1e-2 * float(
                jnp.max(jnp.abs(want))), fault


def test_yarn_frequencies_and_scale_are_the_hand_worked_ones():
    """The published keys: base 10,000 over 64 rotated columns, factor 64
    over 4,096 original positions, beta_fast 32, beta_slow 1, mscale =
    mscale_all_dim = 1. c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 10000):
    c(32) = 10.47, c(1) = 22.51, so pairs 0 to 10 keep their frequency,
    pairs 23 to 31 turn 64 times slower, and pair 16 (f = 1e-2, ramp
    6 / 13) turns at 1e-2 (7 / 13 + 6 / (13 x 64))."""
    freq = yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    assert len(freq) == 32
    low = math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi * 1))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    for i in range(32):
        if i <= 10:
            assert freq[i] == pytest.approx(plain[i], rel=1e-12)
        elif i >= 23:
            assert freq[i] == pytest.approx(plain[i] / 64, rel=1e-12)
        else:
            assert plain[i] / 64 < freq[i] < plain[i]
    assert freq[16] == pytest.approx(1e-2 * (7 / 13 + 6 / (13 * 64)),
                                     rel=1e-9)
    assert freq[16] == pytest.approx(0.00545673, rel=1e-5)
    cfg = Xing4Config.xing4_29b_a4b()
    dec = cfg.decoder()
    assert dec.rope_inv_freq == freq
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert dec.sm_scale == pytest.approx(0.14468, abs=5e-6)
    assert dec.sm_scale == pytest.approx(192 ** -0.5 * 1.41589 ** 2,
                                         rel=1e-5)
    assert yarn_mscale(1.0) == 1.0
    # the reference works them out on its own
    z = reference._sizes_of(cfg)
    assert reference.yarn_frequencies(z) == pytest.approx(list(freq),
                                                          rel=1e-12)
    assert reference.softmax_scale(z) == pytest.approx(dec.sm_scale,
                                                       rel=1e-12)
    # and rope at given frequencies is rope at their base where they are it
    t = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 64))
    from ray_tpu.ops.layers import rope
    _close(rope(t, inv_freq=plain), rope(t, base=10000.0), 1e-6)
    assert float(jnp.max(jnp.abs(rope(t, inv_freq=freq)
                                 - rope(t, base=10000.0)))) > 0.1


def test_a_latent_block_under_remat_keeps_the_latent_not_the_heads(
        monkeypatch, capsys):
    """Under the family's policy a latent layer's block is handed its
    arguments and keeps the kernel's output and lse, the normed latent
    and the rotated shared key, and, where the step has room for the first
    name of KEPT_WHERE_IT_FITS (as here), q: no per-head K or V ([b, h, s,
    24] or [b, h, s, 12]) beyond q and the output; and its gradients are
    the unrematerialised block's."""
    from jax.ad_checkpoint import print_saved_residuals

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = _tiny(balance_tokens=0, hc_sinkhorn_iters=FEW)
    params = xing4_init(jax.random.PRNGKey(0), cfg)
    dec, layer = cfg.decoder(), params["layers"][0]
    assert dec.remat is decoder.keep_kernel_outputs
    base = decoder._kept(decoder.LATENT_ATTENTION)
    assert set(decoder.KEPT_UNDER_REMAT) - set(base) == {
        "flash_attention_k", "flash_attention_v", "flash_attention_q"}
    assert set(base) - set(decoder.KEPT_UNDER_REMAT) == {
        "mla_latent", "mla_k_rope"}
    assert decoder.KEPT_WHERE_IT_FITS["flash_attention_q"] is decoder._first
    kept = (*base, "flash_attention_q")
    assert decoder._kept(decoder.ATTENTION) is decoder.KEPT_UNDER_REMAT
    assert not {"mla_k", "mla_v"} & set(decoder.KEPT_WHERE_IT_FITS)
    plain = functools.partial(decoder._block, dec=dec, kind=dec.kinds[0],
                              mlp=dec.mlp[0])
    block = jax.checkpoint(
        plain, policy=jax.checkpoint_policies.save_only_these_names(*kept))
    b, s, h = 2, 128, cfg.n_heads
    x = tuple(jax.random.normal(jax.random.PRNGKey(2 + i),
                                (b, s, cfg.d_model))
              for i in range(cfg.hc_mult))
    print_saved_residuals(lambda x, layer: block(x, layer, None, None)[0],
                          x, layer)
    lines = capsys.readouterr().out.splitlines()
    shapes = sorted(line.split()[0] for line in lines
                    if "from the argument" not in line
                    and "from a constant" not in line)
    assert f"f32[{b},{s},{cfg.kv_lora_rank}]" in shapes         # mla_latent
    assert f"f32[{b},{s},{cfg.qk_rope_head_dim}]" in shapes     # mla_k_rope
    assert shapes.count(f"f32[{b},{h},{s},{cfg.qk_head_dim}]") == 1   # q
    assert shapes.count(f"f32[{b},{h},{s},{cfg.v_head_dim}]") == 1    # out

    def loss(fn, x, layer):
        return sum(jnp.sum(jnp.sin(t)) for t in fn(x, layer, None, None)[0])

    want = jax.jit(jax.grad(functools.partial(loss, plain),
                            argnums=(0, 1)))(x, layer)
    got = jax.jit(jax.grad(functools.partial(loss, block),
                           argnums=(0, 1)))(x, layer)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 1e-5)


def test_the_plan_counts_a_block_input_at_its_real_width():
    """`remat_plan` at the streams: the base set holds every layer's four
    streams, the reserve what a hyper-connected block's backward holds
    besides (`_streams_hold`), and a capacity too small adds nothing."""
    cfg = _tiny(dtype=jnp.bfloat16, balance_tokens=0)
    dec = cfg.decoder()
    layers = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"]
    one = jax.ShapeDtypeStruct((1, 256, cfg.d_model), cfg.dtype)
    x = (one,) * cfg.hc_mult
    streams = cfg.hc_mult * 256 * cfg.d_model * 2
    assert decoder._rows_and_width(x) == (256, cfg.d_model)
    assert decoder._rows_and_width(one) == (256, cfg.d_model)
    assert decoder._streams_hold(x, layers[0]) == 5 * streams // 2
    assert decoder._streams_hold(one, {"wq": None}) == 0
    plan = decoder.remat_plan(dec, layers, x, cfg.vocab_size, 2 ** 34, 0)
    assert plan.base_bytes >= cfg.n_layers * streams
    assert plan.reserve_bytes >= 5 * streams // 2
    assert plan.layers_extended == cfg.n_layers
    none = decoder.remat_plan(dec, layers, x, cfg.vocab_size, 2 ** 20, 0)
    assert none.layers_extended == 0 and none.kept_extra_bytes == 0


# ---------------------------------------------------------------------------
# a hyper-connected branch's output, kept where it fits
# ---------------------------------------------------------------------------
def test_loss_and_every_gradient_with_the_branches_outputs_kept(
        tiny, monkeypatch):
    """The tiny model with remat on, inside `step_memory` with room for
    everything: the plan is asked and every layer's block keeps
    `hc_channel_out` beside the other candidates; the
    loss and every gradient are the base policy's to the last bit (a kept
    value is the value that was made again), and the unrematerialised
    program's as far as the base policy's are: XLA:CPU fuses one of 43
    gradients another way round a `jax.checkpoint`, 1.5e-8 apart."""
    cfg, params, batch = tiny
    asked = []
    real = decoder.remat_plan

    def spy(*args, **kwargs):
        asked.append(real(*args, **kwargs))
        return asked[-1]

    monkeypatch.setattr(decoder, "remat_plan", spy)

    def step(cfg):
        return _loss_and_gradients(lambda p, b: xing4_loss(p, b, cfg),
                                   params, batch)

    plain = step(dataclasses.replace(cfg, remat=False))
    assert cfg.decoder().remat is decoder.keep_kernel_outputs
    base = step(cfg)
    assert not asked                    # no step's memory: no plan at all
    with attention.step_memory(state_bytes=0, capacity=1 << 40):
        kept = step(cfg)
    plan, = asked
    assert plan.extras == (
        ("hc_channel_out", "mlp_gate_up"),
        ("hc_channel_out", "moe_choice", "moe_shared_up"))
    for g, b, w in zip(*map(jax.tree.leaves, (kept, base, plain))):
        np.testing.assert_array_equal(g, b)
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("index, remade_without, remade_with", [
    (0, set(), set()),
    (1, {"moe_combine", "moe_shared", "moe_route"},
     {"moe_shared", "moe_route"})], ids=["dense", "experts"])
def test_a_block_that_keeps_its_channel_branchs_output_closes_it_once(
        index, remade_without, remade_with, capsys):
    """A hyper-connected block linearised under its base set plus
    `hc_channel_out`: the branch's output [b, L, d] is among the residuals
    under that name, and the compiled backward pass makes nothing of what
    closes the branch again: no second down projection (a dense SwiGLU's,
    the shared expert's), no second combine; what the branch's own
    gradients read (the dense layer's gate and up, the shared expert's up
    projection and activation, the router's weights) still is."""
    from jax.ad_checkpoint import print_saved_residuals

    cfg = _tiny(**CHEAP)
    params = _spread(xing4_init(jax.random.PRNGKey(0), cfg), cfg)
    dec, layer = cfg.decoder(), params["layers"][index]
    plain = functools.partial(decoder._block, dec=dec, kind=dec.kinds[index],
                              mlp=dec.mlp[index])
    b, s = 2, 40
    x = tuple(jax.random.normal(jax.random.PRNGKey(2 + i),
                                (b, s, cfg.d_model))
              for i in range(cfg.hc_mult))

    def remade(extra):
        """(the residuals `write` named, the channel branch's scopes the
        compiled backward runs a second time, its matmuls made again)."""
        block = jax.checkpoint(
            plain, policy=jax.checkpoint_policies.save_only_these_names(
                *decoder._kept(dec.kinds[index]), *extra))
        print_saved_residuals(
            lambda x, layer: block(x, layer, None, None)[0], x, layer)
        named = [line for line in capsys.readouterr().out.splitlines()
                 if line.startswith(f"f32[{b},{s},{cfg.d_model}] output")
                 and "_streams_read.<locals>.write" in line]

        def loss(x, layer):
            return sum(jnp.sum(jnp.sin(t))
                       for t in block(x, layer, None, None)[0])

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            x, layer).compile().as_text()
        again = [line for line in text.splitlines()
                 if "rematted_computation" in line
                 and "channel_mixer" in line]
        scopes = {scope for scope in ("moe_combine", "moe_shared",
                                      "moe_route")
                  if any(f"/{scope}" in line for line in again)}
        return named, scopes, sum(" dot(" in line for line in again)

    named, scopes, dots = remade(())
    assert not named
    named_kept, scopes_kept, dots_kept = remade(("hc_channel_out",))
    assert len(named_kept) == 1     # the value `write` was handed, [b, L, d]
    assert dots_kept < dots     # the down projection(s) run once
    assert (scopes, scopes_kept) == (remade_without, remade_with)


# ---------------------------------------------------------------------------
# the held layer's gated shared branch
# ---------------------------------------------------------------------------
def _expert_case(E=64, d=32, f=16, rows=96, k=4):
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    return dict(
        x=jax.random.normal(ks[0], (rows, d)),
        router=jax.random.normal(ks[1], (d, E)) * d ** -0.5,
        bias=0.05 * jax.random.normal(ks[2], (E,)),
        gate_up=jax.random.normal(ks[3], (E, d, 2 * f)) * d ** -0.5,
        down=jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
        shared_gate_up=jax.random.normal(ks[5], (d, 2 * f)) * d ** -0.5,
        shared_down=jax.random.normal(ks[6], (f, d)) * f ** -0.5, k=k)


def _held(c, first, count, shared=True):
    return held_moe_layer(
        c["x"], c["router"], c["bias"],
        c["gate_up"][first:first + count], c["down"][first:first + count],
        c["shared_gate_up"] if shared else None,
        c["shared_down"] if shared else None, experts_per_token=c["k"],
        first=first, routed_scale=2.0, gated=True, weight_eps=1e-20)


def test_the_eight_eighths_and_the_shared_expert_once_are_the_uncut_layer():
    """What ties the share to the model: experts 0-7, 8-15, ... 56-63 on
    eight chips, each leaving out what the others' would add, and the
    shared expert, which every chip computes alike, counted once, sum to
    the reference's layer with all 64 held; a share with its shared branch
    is the reference's share, and relu^2 in its place is another layer."""
    c = _expert_case()

    def plain(first, count, fault=None):
        return reference._plain_experts(
            c["x"], c["router"], c["bias"],
            c["gate_up"][first:first + count], c["down"][first:first + count],
            c["shared_gate_up"], c["shared_down"], k=c["k"], first=first,
            scale=2.0, fault=fault)[0]

    uncut = plain(0, 64)
    with_shared = _held(c, 0, 8)[0]
    _close(with_shared, plain(0, 8))
    assert float(jnp.max(jnp.abs(
        plain(0, 8, "shared_expert_relu2") - with_shared))) > 1e-2
    eighths = [_held(c, first, 8, shared=False) for first in range(0, 64, 8)]
    shared = with_shared - eighths[0][0]
    _close(sum(out for out, _ in eighths) + shared, uncut)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2
    for _, stats in eighths[1:]:
        np.testing.assert_array_equal(stats["expert_tokens"],
                                      eighths[0][1]["expert_tokens"])
    assert sum(int(s["expert_rows_held"]) for _, s in eighths) == 96 * 4


def test_counts_are_the_hand_computed_ones():
    """The cell's operations from its file, by hand: a layer's latent
    attention products 2 x (3584 x 768 + 768 x 6144 + 3584 x 576 + 512 x
    8192 + 4096 x 3584) = 56.8 M a token, its causal maps 2 x 16384 x 32
    x 320 / 2 = 167.8 M; two hyper-connections 2 x (2 x 14336 x 24 + 2 x
    4 x 3584 + 2 x 20 x 3584) = 1.72 M; the dense SwiGLU 198.2 M; an
    expert layer's router, shared expert and 0.5 held rows 33.5 M; the
    head 117.4 M: 1,581 M forward a token."""
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    products = 2 * (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
                    + 4096 * 3584)
    maps = 2 * 16384 * 32 * 320 / 2
    hyper = 2 * (2 * 14336 * 24 + 2 * 4 * 3584 + 2 * 20 * 3584)
    dense = 6 * 3584 * 9216
    experts = 2 * 3584 * 64 + 6 * 3584 * 1024 + 0.5 * 6 * 3584 * 1024
    head = 2 * 3584 * 16384
    forward = 5 * (products + maps + hyper) + dense + 4 * experts + head
    assert reference.forward_flops_per_token(config, 16384) == forward
    assert forward == pytest.approx(1581e6, rel=1e-3)
    assert 5 * (products + maps) / forward == pytest.approx(0.71, abs=0.005)
    cfg = reference.build(config, balance_tokens=0)
    assert reference.train_flops_per_token(cfg, 16384) == 3 * forward
    assert reference.held_rows_balanced(config, 16384) == 8192
    assert reference.attention_kernel_flops(config, 1, 16384) == \
        5 * 2 * 16384 ** 2 * 32 * 3 * 320 / 2
    assert reference.attention_kernel_bytes(config, 1, 16384) == \
        5 * 6 * 16384 * 32 * 320 * 2
    assert reference.expert_matmul_flops(config, 16384) == \
        4 * 9 * 2 * 8192 * 3584 * 1024
    assert reference.expert_matmul_bytes(config, 16384) == \
        4 * 9 * 2 * (8192 * (3584 + 1024) + 8 * 3584 * 1024)
