"""models.hybrid (granite-4.0-h-micro's family) and ops.ssm_scan against the
plain float32 reference, chipbench/families/granite_hybrid.py: the
recurrence token by token, attention as a masked softmax, nothing shared
with ray_tpu. CPU, `HybridConfig.tiny()` and small shapes, float32 at
highest matmul precision, seeded random weights; the kernels run
interpreted (RAY_TPU_PALLAS_INTERPRET=1) beside their jax.numpy form."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import granite_hybrid as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.hybrid import (HybridConfig, hybrid_forward, hybrid_init,
                                   hybrid_loss, hybrid_param_axes,
                                   make_hybrid_train_step)
from ray_tpu.ops import ssm_scan, ssm_scan_plan, ssm_scan_reference
from ray_tpu.ops.attention import VMEM_BUDGET
from ray_tpu.ops.layers import causal_conv1d_silu, gated_rms_norm

TOL = 1e-4


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["jax", "interpreted"])
def form(request, monkeypatch):
    """Both forms of the scan: the jax.numpy chunked one (what the CPU
    runs) and the Pallas kernels in interpreter mode."""
    if request.param == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    return request.param


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _scan_inputs(L, decay, with_state, b=2, H=4, P=8, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 8)
    x = jax.random.normal(ks[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H)))
    # dt a from -decay / 1000 to -decay a step, a head
    a = -decay * jnp.exp(jnp.linspace(jnp.log(1e-3), 0.0, H)) / jnp.mean(dt)
    B = jax.random.normal(ks[2], (b, L, 1, N))
    C = jax.random.normal(ks[3], (b, L, 1, N))
    D = jax.random.normal(ks[4], (H,))
    init = jax.random.normal(ks[5], (b, H, P, N)) if with_state else None
    weights = (jax.random.normal(ks[6], (b, L, H, P)),
               jax.random.normal(ks[7], (b, H, P, N)))
    return (x, dt, a, B, C, D, init), weights


def _by_recurrence(x, dt, a, B, C, D, init):
    H = x.shape[2]
    return reference.recurrence(x, dt, a, jnp.repeat(B, H, 2),
                                jnp.repeat(C, H, 2), D, init)


@pytest.mark.parametrize("L,chunk,decay,with_state", [
    (8, 8, 1.0, False),          # one chunk
    (32, 8, 1.0, False),         # several: the state crosses boundaries
    (32, 8, 1.0, True),          # from an initial state
    (64, 16, 30.0, True),        # dt a down to -30 a step: decays underflow
    (32, 8, 1e-3, True),         # hardly any decay
], ids=["one-chunk", "chunks", "initial-state", "strong-decay", "weak-decay"])
def test_ssm_scan_equals_the_recurrence_with_every_gradient(
        form, L, chunk, decay, with_state):
    args, (wy, ws) = _scan_inputs(L, decay, with_state)
    diff = [i for i, t in enumerate(args) if t is not None]

    def scalar(fn):
        def f(*given):
            full = list(args)
            for i, t in zip(diff, given):
                full[i] = t
            y, state = fn(*full)
            return jnp.sum(y * wy) + jnp.sum(state * ws), (y, state)
        return jax.value_and_grad(f, argnums=tuple(range(len(diff))),
                                  has_aux=True)

    given = [args[i] for i in diff]
    (_, (y0, s0)), g0 = scalar(_by_recurrence)(*given)
    (_, (y1, s1)), g1 = scalar(
        lambda *t: ssm_scan(*t[:6], chunk, t[6]))(*given)
    _close(y1, y0)
    _close(s1, s0)
    for got, want in zip(g1, g0):
        _close(got, want)


def test_the_kernels_run_when_interpreted_and_the_jax_form_pads(monkeypatch):
    """The interpreted run above is the kernels' (two pallas_calls in its
    jaxpr), and a length that is no whole number of chunks takes the jax
    form, whose padding leaves the state as it is."""
    args, _ = _scan_inputs(32, 1.0, True)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    text = str(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(ssm_scan(x, *args[1:6], 8, args[6])[0])))(args[0]))
    assert text.count("pallas_call") == 2
    ragged = tuple(t[:, :29] if t is not None and t.ndim > 2 else t
                   for t in args[:5]) + args[5:]
    y, state = ssm_scan(*ragged[:6], 8, ragged[6])
    y0, s0 = _by_recurrence(*ragged)
    _close(y, y0)
    _close(state, s0)
    y, state = ssm_scan_reference(*ragged[:6], 8, ragged[6])
    _close(y, y0)


def test_ssm_scan_plan_gives_the_cell_its_sizes():
    plan = ssm_scan_plan(16384, 64, 64, 128, 256)
    assert (plan.chunks, plan.heads_per_block, plan.grid) == (64, 8, (64, 8))
    assert plan.fwd_tiles == 64 * 64 and plan.bwd_tiles == 2 * 64 * 64
    assert plan.state_bytes == 64 * 64 * 64 * 128 * 4      # 134 MB
    # the block is the largest whose estimate fits the budget: 8 heads
    # here, fewer where the tiles are larger, none where nothing fits
    assert 8 * 2 ** 20 < plan.vmem_bytes <= VMEM_BUDGET
    wide = ssm_scan_plan(2048, 448, 64, 128, 512)
    assert wide.heads_per_block == 4 and wide.vmem_bytes <= VMEM_BUDGET
    with pytest.raises(ValueError, match="VMEM"):
        ssm_scan_plan(2048, 64, 64, 128, 1024)
    with pytest.raises(ValueError, match="whole chunks"):
        ssm_scan_plan(1000, 64, 64, 128, 256)


def _plain_conv_silu(x, weight, bias, tail=None):
    """The plain K-shift form, as ops/layers.py had it until PR 34, its
    gradient left to autodiff: what the rule is held to."""
    K, L = weight.shape[1], x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(K):
        y = y + padded[:, k:k + L].astype(jnp.float32) * w[:, k]
    return jax.nn.silu(y.astype(x.dtype)), padded[:, L:]


def _conv_inputs(b, L, C, with_tail, dtype=jnp.float32, K=4, seed=0):
    """(x, weight, bias, tail or None) and weights for y and the new tail:
    a loss that reads both outputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 7 * L + C), 6)
    tail = jax.random.normal(ks[3], (b, K - 1, C)).astype(dtype)
    return ((jax.random.normal(ks[0], (b, L, C)).astype(dtype),
             (jax.random.normal(ks[1], (C, K)) * 0.5).astype(dtype),
             jax.random.normal(ks[2], (C,)).astype(dtype),
             tail if with_tail else None),
            (jax.random.normal(ks[4], (b, L, C)).astype(dtype),
             jax.random.normal(ks[5], (b, K - 1, C)).astype(dtype)))


def _conv_gradients(fn, args, weights):
    """d/d(x, weight, bias[, tail]) of <y, weights[0]> + <new tail,
    weights[1]>, in float32."""
    def loss(*given):
        y, tail = fn(*given)
        return (jnp.sum(y.astype(jnp.float32) * weights[0])
                + jnp.sum(tail.astype(jnp.float32) * weights[1]))

    given = args if args[3] is not None else args[:3]
    return jax.jit(jax.grad(loss, argnums=tuple(range(len(given)))))(*given)


# b in {1, 2}; L shorter than the taps, one token (decode) and longer; with
# and without a tail; C under and over a lane's 128, a multiple of neither
CONV_CASES = [(1, 12, 6, False), (2, 12, 6, True), (2, 2, 6, False),
              (1, 2, 6, True), (2, 1, 6, True), (1, 3, 130, True),
              (2, 37, 130, False), (2, 37, 130, True)]


def test_causal_conv1d_silu_and_its_tail():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (2, 12, 6))
    w, bias = jax.random.normal(ks[1], (6, 4)), jax.random.normal(ks[2], (6,))
    y, tail = causal_conv1d_silu(x, w, bias)
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = reference._silu(
        bias + sum(padded[:, k:k + 12] * w[:, k] for k in range(4)))
    _close(y, want)
    np.testing.assert_array_equal(tail, x[:, -3:])
    # in two pieces, the tail handed on, it is the same convolution
    y1, t1 = causal_conv1d_silu(x[:, :5], w, bias)
    y2, t2 = causal_conv1d_silu(x[:, 5:], w, bias, t1)
    _close(jnp.concatenate([y1, y2], 1), want)
    np.testing.assert_array_equal(t2, tail)
    y3, t3 = causal_conv1d_silu(x[:, :2], w, bias)      # shorter than the taps
    np.testing.assert_array_equal(t3[:, 1:], x[:, :2])
    np.testing.assert_array_equal(t3[:, :1], 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,L,C,with_tail", CONV_CASES)
def test_conv_rule_forward_is_the_plain_form_bit_for_bit(b, L, C, with_tail,
                                                         dtype):
    args, _ = _conv_inputs(b, L, C, with_tail, dtype)
    for got, want in zip(jax.jit(causal_conv1d_silu)(*args),
                         jax.jit(_plain_conv_silu)(*args)):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("b,L,C,with_tail", CONV_CASES)
def test_conv_rule_gradients_equal_autodiff_of_the_plain_form(b, L, C,
                                                              with_tail):
    args, weights = _conv_inputs(b, L, C, with_tail)
    got = _conv_gradients(causal_conv1d_silu, args, weights)
    want = _conv_gradients(_plain_conv_silu, args, weights)
    assert len(got) == (4 if with_tail else 3)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w, 1e-5)


@pytest.mark.parametrize("weights_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("b,with_tail", [(1, False), (2, True)])
def test_conv_rule_in_bfloat16_is_no_further_from_float32_than_autodiff(
        b, with_tail, weights_dtype):
    """bf16 in and out, as the cells run it: each of the rule's gradients
    lies no further from the float32 gradient at the same inputs than
    autodiff of the plain form does (which rounds g, and then each tap's
    product, to bfloat16 before it adds them)."""
    (x, w, bias, tail), weights = _conv_inputs(b, 512, 130, with_tail,
                                               jnp.bfloat16)
    args = (x, w.astype(weights_dtype), bias.astype(weights_dtype), tail)
    exact = _conv_gradients(
        _plain_conv_silu,
        tuple(None if t is None else t.astype(jnp.float32) for t in args),
        weights)
    rule = _conv_gradients(causal_conv1d_silu, args, weights)
    plain = _conv_gradients(_plain_conv_silu, args, weights)

    def distance(got, want):
        assert got.dtype in (jnp.bfloat16, weights_dtype)
        return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                     / jnp.linalg.norm(want))

    for name, r, p, e in zip(("x", "weight", "bias", "tail"), rule, plain,
                             exact):
        assert distance(r, e) <= distance(p, e), name
        assert distance(r, e) < 1e-2, name


@pytest.mark.parametrize("cut", [1, 2, 5, 11])
def test_a_sequence_in_two_calls_is_one_call_values_and_gradients(cut):
    """The tail handed from one call to the next: the same values bit for
    bit, and the same gradients by x, the taps, the bias and the first
    call's tail."""
    (x, w, bias, tail), weights = _conv_inputs(2, 12, 6, True)

    def two_calls(x, w, bias, tail):
        y1, t1 = causal_conv1d_silu(x[:, :cut], w, bias, tail)
        y2, t2 = causal_conv1d_silu(x[:, cut:], w, bias, t1)
        return jnp.concatenate([y1, y2], axis=1), t2

    for got, want in zip(two_calls(x, w, bias, tail),
                         causal_conv1d_silu(x, w, bias, tail)):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(
            _conv_gradients(two_calls, (x, w, bias, tail), weights),
            _conv_gradients(_plain_conv_silu, (x, w, bias, tail), weights)):
        _close(got, want, 1e-5)


def test_gated_norm_gates_first_and_norms_once():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    y = jax.random.normal(ks[0], (2, 5, 16))
    z = jax.random.normal(ks[1], (2, 5, 16))
    w = jax.random.normal(ks[2], (16,))
    _close(gated_rms_norm(y, z, w, 1e-5),
           reference._rms_norm(y * reference._silu(z), w, 1e-5))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """(cfg, params) in float32, no norm weight or bias left at its
    initial 1 or 0."""
    cfg = dataclasses.replace(HybridConfig.tiny(), dtype=jnp.float32,
                              remat=False)
    params = hybrid_init(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda t: t + 0.1 * jax.random.normal(next(keys), t.shape)
        if t.ndim == 1 else t, params)
    return cfg, params


def _tokens(cfg, batch=2, seq=32, seed=2):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                             cfg.vocab_size)
    return tok, jnp.roll(tok, -1, 1)


def test_config_presets_and_parameter_tree(tiny):
    cfg, params = tiny
    assert cfg.layer_types == ("mamba", "mamba", "attention")
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.attention_multiplier) == (
        4, 1 / 64)
    full = HybridConfig.granite_4_0_h_micro()
    assert full.n_layers == 40
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert (full.mamba_inner, full.mamba_conv_dim) == (4096, 4352)
    shapes = jax.eval_shape(lambda: hybrid_init(jax.random.PRNGKey(0), full))
    count = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(shapes))
    assert abs(count - 3.19e9) < 0.01e9
    per = [sum(int(np.prod(t.shape)) for t in jax.tree.leaves(lay))
           for lay in shapes["layers"][4:6]]
    assert [round(n / 1e6, 1) for n in per] == [76.2, 60.8]
    # the axes tree has the parameters' structure, layer by layer
    axes = hybrid_param_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda t: isinstance(t, tuple))
    assert "head" not in params                       # tied


def test_mixers_equal_the_reference_layer_by_layer(tiny, form):
    cfg, params = tiny
    dec = cfg.decoder()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    for layer in params["layers"]:
        y = reference._rms_norm(x, layer["ln1"], cfg.norm_eps)
        if "in_proj" in layer:
            got, _ = decoder.mamba2(x, layer, dec)
            want, _ = reference._mamba2(y, layer, cfg)
        else:
            # no rotary, scores scaled by 1/64, each kv head serving four
            got, _ = decoder.attention(x, layer, dec)
            want = reference._attention(y, layer, cfg)
        _close(got, want)


def test_attention_has_no_positions_at_all(tiny):
    """Without rotary the only order attention knows is the causal mask:
    permuting the earlier tokens leaves the last position's output."""
    cfg, params = tiny
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, cfg.d_model))
    swapped = x.at[:, [2, 9]].set(x[:, [9, 2]])
    a, _ = decoder.attention(x, layer, cfg.decoder())
    b, _ = decoder.attention(swapped, layer, cfg.decoder())
    _close(a[:, -1], b[:, -1])


def test_logits_state_and_every_gradient_equal_the_reference(tiny, form):
    cfg, params = tiny
    tok, tgt = _tokens(cfg)
    _close(hybrid_forward(params, tok, cfg),
           reference.reference_logits(params, tok, cfg))
    want = jax.grad(lambda p: reference.reference_loss(p, tok, tgt, cfg))(
        params)
    got = jax.grad(lambda p: hybrid_loss(p, (tok, tgt), cfg))(params)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        assert float(jnp.max(jnp.abs(flat_want[path]))) > 0, path
        _close(g, flat_want[path])
    _close(hybrid_loss(params, (tok, tgt), cfg),
           reference.reference_loss(params, tok, tgt, cfg))
    # the state every Mamba-2 layer is left in, through a prefill
    _, cache = cached_forward(
        params, tok, init_cache(cfg, 2, 32), 0, cfg)
    states = [c["ssm"] for c in cache if "ssm" in c]
    for got_s, want_s in zip(states, reference.reference_final_states(
            params, tok, cfg), strict=True):
        _close(got_s, want_s)


def test_the_all_bfloat16_reference_is_another_number(tiny):
    cfg, params = tiny
    tok, tgt = _tokens(cfg)
    exact = reference.reference_loss(params, tok, tgt, cfg)
    low = reference.reference_loss(params, tok, tgt, cfg, jnp.bfloat16)
    assert low.dtype == jnp.float32
    assert 1e-4 < abs(float(exact) - float(low)) < 0.1


@pytest.mark.parametrize("fault", [None, *reference.STRUCTURAL_FAULTS])
def test_the_cell_holds_the_scan_to_a_limit_of_its_own(form, fault):
    """families/granite_hybrid.py `hold_kernels`, what the cell does on the
    chip before it hands the program over: the scan as models.decoder
    calls it, in the model's bfloat16, against the float32 recurrence
    (y, final state, every gradient). The program is inside KERNEL_LIMIT,
    each planted structural fault and the all-bfloat16 recurrence
    outside it."""
    from chipbench.harness import BenchFailure

    cfg = HybridConfig.tiny()
    assert cfg.dtype == jnp.bfloat16
    if fault is None:
        reference.hold_kernels(cfg)
        low = reference.kernel_errors(cfg, low=True)
        assert max(low.values()) > reference.KERNEL_LIMIT, low
    else:
        with reference.planted(fault), pytest.raises(BenchFailure,
                                                     match="scan is off"):
            reference.hold_kernels(cfg)
        assert decoder.ssm_scan is ssm_scan       # planted() put it back


def test_remat_on_equals_remat_off(tiny, form):
    cfg, params = tiny
    batch = _tokens(cfg)
    on = dataclasses.replace(cfg, remat=True)
    g_off = jax.grad(lambda p: hybrid_loss(p, batch, cfg))(params)
    g_on = jax.grad(lambda p: hybrid_loss(p, batch, on))(params)
    for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        _close(a, b, 1e-6)


def test_prefill_then_decode_equals_the_full_forward(tiny, form):
    """Both kinds of state in one cache: a prefill of 13 tokens (not a
    whole number of chunks), then one token at a time."""
    cfg, params = tiny
    tok, _ = _tokens(cfg)
    full = hybrid_forward(params, tok, cfg)
    cache = init_cache(cfg, 2, 32)
    assert [sorted(c) for c in cache] == [["conv", "ssm"], ["conv", "ssm"],
                                          ["k", "v"]]
    assert cache[0]["conv"].shape == (2, 3, cfg.mamba_conv_dim)
    assert cache[0]["ssm"].shape == (2, 4, 32, 16)
    assert cache[0]["ssm"].dtype == jnp.float32
    assert cache[2]["k"].shape == (2, 1, 32, 16)       # kv heads, not 4
    logits, cache = cached_forward(params, tok[:, :13], cache, 0, cfg)
    out = [logits]
    for t in range(13, 32):
        logits, cache = cached_forward(params, tok[:, t:t + 1], cache, t, cfg)
        out.append(logits)
    _close(jnp.concatenate(out, 1), full)
    # a second chunk of prefill continues from the cached state too
    cache = init_cache(cfg, 2, 32)
    first, cache = cached_forward(params, tok[:, :16], cache, 0, cfg)
    second, _ = cached_forward(params, tok[:, 16:], cache, 16, cfg)
    _close(jnp.concatenate([first, second], 1), full)


def test_a_mamba_block_keeps_the_scan_kernels_outputs_and_nothing_else(
        tiny, monkeypatch, capsys):
    """Under the family's remat policy a Mamba-2 block's backward pass is
    handed its arguments and the two values the scan kernel made
    (`ssm_scan_y`, `ssm_scan_states`): the projections, the convolution
    and the gated norm are made again."""
    import functools

    from jax.ad_checkpoint import print_saved_residuals

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg, params = tiny
    dec = dataclasses.replace(cfg, remat=True).decoder()
    layer = params["layers"][0]
    block = jax.checkpoint(
        functools.partial(decoder._block, dec=dec, kind=dec.kinds[0],
                          mlp=dec.mlp[0]),
        policy=dec.remat)
    b, s = 2, 32
    print_saved_residuals(lambda x, layer: block(x, layer, None, None)[0],
                          jnp.ones((b, s, cfg.d_model)), layer)
    lines = capsys.readouterr().out.splitlines()
    kept = sorted(line.split()[0] for line in lines
                  if "from the argument" not in line
                  and "from a constant" not in line)
    pairs, chunks = cfg.mamba_n_heads // 2, s // cfg.mamba_chunk_size
    assert kept == sorted([
        f"f32[{b},{s},{cfg.mamba_inner}]",                      # ssm_scan_y
        f"f32[{b},{chunks},{pairs},{cfg.mamba_d_state},"
        f"{2 * cfg.mamba_d_head}]"])                            # .._states


@pytest.mark.parametrize("kinds", [("mamba", "mamba"),
                                   ("attention", "attention")])
def test_a_model_of_one_kind_of_layer_needs_no_special_case(kinds):
    cfg = dataclasses.replace(HybridConfig.tiny(), layer_types=kinds,
                              dtype=jnp.float32)
    params = hybrid_init(jax.random.PRNGKey(0), cfg)
    tok, _ = _tokens(cfg, seq=16)
    cache = init_cache(cfg, 2, 16)
    logits, cache = cached_forward(params, tok[:, :8], cache, 0, cfg)
    more, _ = cached_forward(params, tok[:, 8:], cache, 8, cfg)
    _close(jnp.concatenate([logits, more], 1),
           hybrid_forward(params, tok, cfg))


def test_train_step_runs_each_scan_kernel_once_a_layer(monkeypatch):
    """The lowered train step of a stack with two Mamba-2 layers, remat on,
    calls the scan's forward kernel twice and its backward twice: the
    blocks keep `ssm_scan_y` and `ssm_scan_states`, so no forward runs
    again. (tests/test_compile_v5e_granite.py counts the compiled step of
    the cell with profiling.kernel_calls: 9 and 9.)"""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = HybridConfig(vocab_size=512, d_model=128, n_heads=2, n_kv_heads=1,
                       layer_types=("mamba", "attention", "mamba"), d_ff=256,
                       mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128,
                       mamba_chunk_size=128, max_seq_len=256, remat=True)
    assert {"ssm_scan_y", "ssm_scan_states"} <= set(decoder.KEPT_UNDER_REMAT)
    init_state, step = make_hybrid_train_step(cfg)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    text = step.trace(state, (tok, tok)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == set(
        reference.MOSAIC_KERNELS)
    calls = re.findall(r"call @(_scan_forward_call|_scan_backward_call)\b",
                       text)
    assert sorted(calls) == ["_scan_backward_call"] * 2 + [
        "_scan_forward_call"] * 2


def test_tiny_train_step_learns():
    cfg = HybridConfig.tiny()
    init_state, step = make_hybrid_train_step(cfg, donate=False)
    state = init_state(jax.random.PRNGKey(0))
    batch = _tokens(cfg, batch=2, seq=32)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_counts_equal_hand_counts_at_the_published_sizes():
    """6.0 GFLOP a token at 16,384 positions of the ten-layer cut, by
    hand: a Mamba-2 layer 2 x 2048 x 8512 (in) + 2 x 4 x 4352 (conv) +
    (256 x 128 + 256 x 4096 + 4 x 4096 x 128) (scan) + 2 x 4096 x 2048
    (out) + 6 x 2048 x 8192 (MLP); the attention layer 2 x 2048 x (2048 +
    1024 + 2048) + 2 x 16384 x 2048 + the MLP; the head 2 x 2048 x 100352."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    scan = 256 * 128 + 256 * 4096 + 4 * 4096 * 128
    mlp = 6 * 2048 * 8192
    mamba = 2 * 2048 * 8512 + 2 * 4 * 4352 + scan + 2 * 4096 * 2048 + mlp
    attn = 2 * 2048 * (2048 + 1024 + 2048) + 2 * 16384 * 2048 + mlp
    forward = 9 * mamba + attn + 2 * 2048 * 100352
    assert reference.forward_flops_per_token(config, 16384) == forward
    assert reference.train_flops_per_token(config, 16384) == 3 * forward
    assert abs(3 * forward - 6.0e9) < 0.05e9
    cfg = reference.build(config)
    assert reference.train_flops_per_token(cfg, 16384) == 3 * forward
    assert reference.ssm_scan_flops(config, 1, 16384) == 9 * 3 * 16384 * scan
    token = (2 * 4096 * 2 + 64 * 4 + 256 * 2) + (
        3 * 4096 * 2 + 2 * 64 * 4 + 2 * 256 * 2)
    assert reference.ssm_scan_bytes(config, 1, 16384) == 9 * (
        16384 * token + 2 * 64 * 4096 * 128 * 4)
    assert reference.attention_kernel_flops(config, 1, 16384) == (
        6 * 2 * 16384 ** 2 * 2048 / 2)
    assert reference.attention_kernel_bytes(config, 1, 16384) == (
        6 * 16384 * 2048 * 2 + 6 * 16384 * 512 * 2)
