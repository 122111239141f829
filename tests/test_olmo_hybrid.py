"""models.olmo_hybrid (Olmo-Hybrid-7B's family) against the plain float32
reference, chipbench/families/olmo_hybrid.py: the delta rule token by
token, attention as a masked softmax with q and k normed over all their
columns, a block that norms what its branches return beside blocks that
norm what they read, nothing shared with ray_tpu. CPU,
`OlmoHybridConfig.tiny()`, float32 at highest matmul precision, seeded
random weights; the kernels run interpreted (RAY_TPU_PALLAS_INTERPRET=1)
beside their jax.numpy form."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import olmo_hybrid as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridConfig,
                                        make_olmo_hybrid_train_step,
                                        olmo_hybrid_forward,
                                        olmo_hybrid_init, olmo_hybrid_loss,
                                        olmo_hybrid_param_axes)
from ray_tpu.ops.layers import (causal_conv1d_silu, gated_rms_norm,
                                head_rms_norm_gated)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Float32 rounding through four layers, three of them 32 dependent steps
# of a state, against a reference that sums in another order: 2e-5 of the
# largest logit seen here. An all-bfloat16 state and decay move the logits
# by 1e-2 (test_an_all_bfloat16_reference_fails_the_tolerance).
TOL = 1e-4


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["jax", "interpreted"])
def form(request, monkeypatch):
    if request.param == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    return request.param


def _cfg(**kw):
    return dataclasses.replace(OlmoHybridConfig.tiny(), dtype=jnp.float32,
                               remat=False, **kw)


@functools.lru_cache(maxsize=None)
def _seeded(cfg, seq=32, batch=2, seed=0):
    """Weights with every norm weight, A_log and dt_bias moved off their
    initial values, so that none multiplies by one unseen. Made once a
    (configuration, shape, seed) for the module: every case reads the
    same arrays."""
    params = olmo_hybrid_init(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if leaf.ndim == 1 and name not in ("A_log", "dt_bias"):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(shake, params)
    tok = jax.random.randint(jax.random.PRNGKey(seed + 2), (batch, seq), 0,
                             cfg.vocab_size)
    return params, tok


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


@functools.lru_cache(maxsize=None)
def _wanted_logits(cfg):
    """The reference's logits on `_seeded(cfg)`, one jitted program, made
    once for every case and form that reads them (the reference does not
    know the forms of the rule apart)."""
    params, tok = _seeded(cfg)
    return jax.jit(lambda p: reference.reference_logits(p, tok, cfg))(params)


@functools.lru_cache(maxsize=None)
def _wanted_loss_and_gradients(cfg):
    params, tok = _seeded(cfg)
    return jax.jit(jax.value_and_grad(lambda p: reference.reference_loss(
        p, tok, jnp.roll(tok, -1, 1), cfg)))(params)


@functools.lru_cache(maxsize=None)
def _wanted_final_states(cfg):
    params, tok = _seeded(cfg)
    return jax.jit(lambda p: reference.reference_final_states(
        p, tok, cfg))(params)


def test_logits_match_the_reference(form):
    cfg = _cfg()
    params, tok = _seeded(cfg)
    _close(jax.jit(lambda p: olmo_hybrid_forward(p, tok, cfg))(params),
           _wanted_logits(cfg))


def test_loss_and_every_gradient_match_the_reference(form):
    cfg = _cfg()
    params, tok = _seeded(cfg)
    tgt = jnp.roll(tok, -1, 1)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: olmo_hybrid_loss(p, (tok, tgt), cfg)))(params)
    want, want_g = _wanted_loss_and_gradients(cfg)
    assert abs(float(got) - float(want)) <= 1e-5
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = jax.tree.leaves(want_g)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        # relative to the gradient's own largest entry: 1e-3 is float32
        # rounding through the backward of a 32-step state
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path          # every parameter is reached
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-3 * scale, path


def test_an_all_bfloat16_reference_fails_the_tolerance():
    """The tolerance is tight enough to tell: the reference with every
    parameter and value in bfloat16 is off by more than TOL."""
    cfg = _cfg()
    params, tok = _seeded(cfg)
    want = _wanted_logits(cfg)
    low, head, _ = jax.jit(lambda p: reference._hidden(
        p, tok, cfg, jnp.bfloat16))(params)
    got = (low @ head).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * TOL * float(
        jnp.max(jnp.abs(want)))


def test_final_states_match_the_reference(form):
    cfg = _cfg()
    params, tok = _seeded(cfg)
    cache = init_cache(cfg, tok.shape[0], tok.shape[1])
    _, cache = jax.jit(lambda p, cache: cached_forward(
        p, tok, cache, 0, cfg))(params, cache)
    want = _wanted_final_states(cfg)
    got = [c["delta"] for c in cache if "delta" in c]
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        _close(g, w)


@pytest.mark.parametrize("prefill", [8, 19])
def test_prefill_then_decode_matches_the_full_forward(form, prefill):
    """Both kinds of state in one cache list: three layers' convolution
    tails and delta-rule states, which do not grow, and one layer's keys
    and values. A prefill of a whole number of chunks (the kernels) and of
    19 tokens (the jax.numpy form pads), then one token at a time."""
    cfg = _cfg()
    params, tok = _seeded(cfg)
    want = _wanted_logits(cfg)
    cache = init_cache(cfg, tok.shape[0], tok.shape[1])
    assert [sorted(c) for c in cache] == [["conv", "delta"]] * 3 + [
        ["k", "v"]]
    assert cache[0]["delta"].shape == (2, 3, 12, 20)
    assert cache[0]["conv"].shape == (2, 3, 3 * (2 * 12 + 20))
    # one jitted program a shape: the prefill's, and the decode step's for
    # every token after it
    forward = jax.jit(lambda p, toks, cache, at: cached_forward(
        p, toks, cache, at, cfg))
    logits, cache = forward(params, tok[:, :prefill], cache, 0)
    outs = [logits]
    for t in range(prefill, tok.shape[1]):
        logits, cache = forward(params, tok[:, t:t + 1], cache, t)
        outs.append(logits)
    _close(jnp.concatenate(outs, axis=1), want)


def test_the_norms_sit_where_the_weights_say():
    """A linear-attention layer holds `ln1`, `ln2` (norms on what its
    branches read), the full-attention layer `post_attention`,
    `post_feedforward` and neither of the others (norms on what they
    return): `_block` is one function and reads the place off the
    weights."""
    cfg = _cfg()
    params, _ = _seeded(cfg)
    kinds = [LINEAR, LINEAR, LINEAR, FULL]
    assert list(cfg.layer_types) == kinds
    for kind, layer in zip(kinds, params["layers"]):
        pre = {"ln1", "ln2"} <= set(layer)
        post = {"post_attention", "post_feedforward"} <= set(layer)
        assert pre == (kind == LINEAR) and post == (kind == FULL)
        assert ("delta_in" in layer) == (kind == LINEAR)
        assert "conv_b" not in layer
    # by hand, the post-norm block of the last layer
    layer, dec = params["layers"][3], cfg.decoder()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    got = decoder._block(x, layer, None, None, dec=dec,
                         kind=dec.kinds[3], mlp=dec.mlp[3])[0]
    a, _ = decoder.attention(x, layer, dec)
    h = x + decoder.rms_norm(a, layer["post_attention"], cfg.norm_eps)
    out, _ = dec.mlp[3](h, layer)
    _close(got, h + decoder.rms_norm(out, layer["post_feedforward"],
                                     cfg.norm_eps), tol=1e-6)


def test_rope_theta_rotates_the_full_attention_layer():
    """Built, with no cell: under a rope_theta the full-attention layer's
    q and k are rotated (OLMo's 500,000 is the alternative reading of
    config.json's null), and the logits move."""
    params, tok = _seeded(_cfg())
    plain = olmo_hybrid_forward(params, tok, _cfg())
    rotated = olmo_hybrid_forward(params, tok, _cfg(rope_theta=500000.0))
    assert float(jnp.max(jnp.abs(plain - rotated))) > 1e-3


def test_head_norm_then_gate_is_not_gate_then_norm():
    o = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 20))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 60))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (20,))
    got = head_rms_norm_gated(o, gate, w, 1e-6)
    want = (reference._rms_norm(o, w, 1e-6).reshape(2, 5, 60)
            * reference._silu(gate))
    _close(got, want, tol=1e-6)
    other = gated_rms_norm(o.reshape(2, 5, 60), gate, jnp.tile(w, 3), 1e-6)
    assert float(jnp.max(jnp.abs(got - other))) > 0.1


def test_convolution_without_a_bias_adds_nothing_for_it():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    tail = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 6))

    def both(bias):
        def f(x, w, tail):
            y, new_tail = causal_conv1d_silu(x, w, bias, tail)
            return jnp.sum(y * jnp.cos(y)), new_tail
        (s, new_tail), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(x, w, tail)
        return (s, new_tail, *grads)

    for got, want in zip(both(None), both(jnp.zeros((6,)))):
        _close(got, want, tol=1e-6)
    # no zeros vector is read and summed: one addition fewer is traced
    def adds(bias):
        return str(jax.make_jaxpr(
            lambda x, w: causal_conv1d_silu(x, w, bias)[0])(x, w)).count(
                " add ")
    assert adds(None) == adds(jnp.zeros((6,))) - 1 == 3


def test_parameter_count_is_the_models():
    """ISSUE 41's arithmetic: 24 linear-attention layers of 215.6 M, 8
    full-attention layers of 185.8 M, 770.7 M in the embedding and the
    untied head: 7.43 B."""
    cfg = OlmoHybridConfig.olmo_hybrid_7b()
    shapes = jax.eval_shape(
        lambda: olmo_hybrid_init(jax.random.PRNGKey(0), cfg))
    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    d, f, H, K, V = 3840, 11008, 30, 96, 192
    mlp = 3 * d * f
    linear = (2 * d * H * K + 3 * d * H * V + 2 * d * H + 4 * H * (2 * K + V)
              + 2 * H + V + 2 * d + mlp)
    full = 4 * d * d + 4 * d + mlp
    assert count(shapes["layers"][0]) == linear
    assert count(shapes["layers"][3]) == full
    assert round(linear / 1e6, 1) == 215.6 and round(full / 1e6, 1) == 185.8
    total = 24 * linear + 8 * full + 2 * 100352 * d + d
    assert count(shapes) == total
    assert round(total / 1e9, 2) == 7.43
    assert jax.tree.structure(shapes) == jax.tree.structure(
        olmo_hybrid_param_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))


def test_train_step_learns_and_calls_no_kernel_twice(form):
    cfg = dataclasses.replace(OlmoHybridConfig.tiny(), dtype=jnp.float32)
    init_state, step = make_olmo_hybrid_train_step(cfg, donate=False)
    state = init_state(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def _config_file():
    with open(os.path.join(ROOT, "chipbench/configs/olmo-hybrid-7b.json")) as f:
        return json.load(f)


def test_build_gives_the_published_widths():
    cfg = reference.build(_config_file())
    full = OlmoHybridConfig.olmo_hybrid_7b()
    assert dataclasses.replace(
        cfg, vocab_size=full.vocab_size,
        layer_types=full.layer_types) == full
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert cfg.vocab_size * 4 == full.vocab_size


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("rope_parameters", {"rope_theta": 500000.0}),
    ("linear_allow_neg_eigval", False), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("num_key_value_heads", 6)])
def test_build_refuses_what_the_program_does_not_implement(key, value):
    config = {**_config_file(), key: value}
    with pytest.raises(ValueError, match="only"):
        reference.build(config)


def test_counts_are_the_formulas():
    config = _config_file()
    H, K, V, S = 30, 96, 192, 16384
    assert reference.gated_delta_flops(config, 1, S) == (
        3 * S * H * (6 * K * V + 12 * K * V))
    assert reference.gated_delta_bytes(config, 1, S) == 3 * S * (
        4 * H * K * 2 + 4 * H * V * 2 + 4 * H * 4)
    # the config object gives what the file's dict gives
    cfg = reference.build(config)
    for fn in (reference.train_flops_per_token, ):
        assert fn(cfg, S) == fn(config, S)
    for fn in (reference.gated_delta_flops, reference.gated_delta_bytes,
               reference.attention_kernel_flops,
               reference.attention_kernel_bytes):
        assert fn(cfg, 1, S) == fn(config, 1, S)
    # ISSUE 41's count by required operations: 98 TFLOP a step, the MLPs
    # half of it, the delta rule itself half a percent
    step = reference.train_flops_per_token(config, S) * S
    assert 97e12 < step < 100e12
    assert 0.49 < 4 * 3 * 6 * 3840 * 11008 * S / step < 0.52
    assert 0.004 < reference.gated_delta_flops(config, 1, S) / step < 0.006
