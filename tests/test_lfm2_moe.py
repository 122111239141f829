"""models.lfm2_moe (LFM2-8B-A1B style: gated short convolutions beside
grouped-query attention with a norm a head, a dense SwiGLU layer and then
a held share of SwiGLU experts behind a sigmoid router with a selection
bias, no shared expert) against the benchmark's plain float32 reference
(chipbench/families/lfm2_moe.py) on seeded weights, and the pieces this
family brought to ops/, parallel/ and models/decoder.py: the gated short
convolution in its three forms (jax.numpy, the Pallas kernels interpreted,
a cache's tail), the norm a head, gated experts in the held-expert layer,
a channel mixer named per layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import lfm2_moe as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.lfm2_moe import (Lfm2MoeConfig, lfm2_moe_forward,
                                     lfm2_moe_init, lfm2_moe_loss,
                                     lfm2_moe_loss_and_counters,
                                     lfm2_moe_param_axes,
                                     make_lfm2_moe_train_step, split_bias,
                                     with_bias)
from ray_tpu.ops.layers import head_rms_norm, rope
from ray_tpu.ops.short_conv import gated_short_conv
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import (balance_bias, held_moe_layer,
                                  held_rows_plan, router_scores)

# float32 program against float32 reference: the same sums in another
# order (sorted rows and grouped products against every expert on every
# token; flash attention's blocks against one softmax).
TOL = 1e-4
# bfloat16 program against the float32 reference on a loss of 6: the
# program reads 1.1e-3 off at most over three seeds, this file's reference
# with every value in bfloat16 7.6e-4; a gradient, as a share of its
# largest value, 0.036 at most (the all-bfloat16 reference 0.059): a
# rounding is 2^-8 and a gradient is some ten of them deep. The limits
# that separate the precisions are the cell's, read on the chip.
TOL_BF16_LOSS = 3e-3
TOL_BF16 = 6e-2


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


@pytest.fixture(params=["gathered", "scattered"])
def back(request, monkeypatch):
    """Both ways a pass's rows are added back to their tokens, at shapes
    whose plan would name one: the plan's bound moved past every shape, or
    under all (`held_rows_plan`; parallel/moe.py `_gathered_back`)."""
    monkeypatch.setattr(moe, "_GATHERED_BACK_UP_TO",
                        {"gathered": 10 ** 9, "scattered": 0}[request.param])
    return request.param


def _tiny(dtype=jnp.float32, **changes):
    # 128 wide: the convolution's kernels take whole 128-lane tiles
    return dataclasses.replace(Lfm2MoeConfig.tiny(), d_model=128,
                               head_dim=32, dtype=dtype, **changes)


@pytest.fixture(scope="module")
def tiny():
    """(config in float32, its seeded weights with the balanced biases, a
    batch of two 40-token sequences: not a whole number of row blocks)."""
    cfg = _tiny()
    params = lfm2_moe_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                             cfg.vocab_size)
    return cfg, params, (tok, jnp.roll(tok, -1, 1))


@pytest.fixture(scope="module")
def reference_of_tiny(tiny):
    """(the reference's loss, its gradient by every parameter), once for
    both forms of the program."""
    cfg, params, batch = tiny
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference.reference_loss(p, *batch, cfg))(params)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    if not want.size:
        return
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
def _conv_case(b, S, d, K, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, S, 3 * d)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (K, d)),
            jax.random.normal(ks[2], (b, S, d)))


def _every(fn, wy):
    """y and the gradients of a seeded weighted sum of it by B | C | x and
    the taps: (y, dB, dC, dx, dtaps)."""
    def scalar(bcx, taps):
        y = fn(bcx, taps)
        return jnp.sum(y.astype(jnp.float32) * wy), y
    def run(bcx, taps):
        (_, y), (dbcx, dtaps) = jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True)(bcx, taps)
        return (y, *jnp.split(dbcx, 3, axis=-1), dtaps)
    return jax.jit(run)


@pytest.mark.parametrize("b,S,d,K", [
    (2, 40, 128, 3),        # the model's three taps; one ragged block
    (2, 1100, 256, 3),      # two whole row blocks and a ragged third
    (1, 64, 128, 4),        # K taps: what causal_conv1d_silu's cells have
    (3, 16, 384, 2),        # one halo's rows, three column blocks
], ids=["ragged", "three-blocks", "four-taps", "one-halo"])
def test_conv_is_three_shifted_products_with_every_gradient(form, b, S, d,
                                                            K):
    """Both forms (jax.numpy; the two kernels interpreted) against the
    reference's shifted products: y, dB, dC, dx and the taps' gradient, on
    a batch of sequences whose length is no whole number of row blocks."""
    bcx, taps, wy = _conv_case(b, S, d, K)
    want = _every(reference.gated_conv, wy)(bcx, taps)
    got = _every(lambda a, w: gated_short_conv(a, w)[0], wy)(bcx, taps)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)


def test_conv_kernels_in_bfloat16_round_as_the_plain_form(monkeypatch):
    """In the model's dtype the kernels and the jax.numpy form round in
    the same places (u once, y once): a bfloat16 rounding apart at most,
    and both that near the float32 reference."""
    bcx, taps, wy = _conv_case(2, 200, 128, 3, jnp.bfloat16)
    want = _every(reference.gated_conv, wy)(bcx.astype(jnp.float32), taps)
    plain = _every(lambda a, w: gated_short_conv(a, w)[0], wy)(bcx, taps)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    kernels = _every(lambda a, w: gated_short_conv(a, w)[0], wy)(bcx, taps)
    for k, p, w in zip(kernels, plain, want):
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(
            k.astype(jnp.float32) - p.astype(jnp.float32)))) <= 2 ** -7 * scale
        assert float(jnp.max(jnp.abs(k.astype(jnp.float32) - w))) \
            <= 2 ** -6 * scale


def test_conv_reads_nothing_across_the_sequences_of_a_batch(form):
    """A batch of two is each sequence alone: the second's first rows see
    zeros, not the first's last, forward and backward."""
    bcx, taps, wy = _conv_case(2, 48, 128, 3)
    both = _every(lambda a, w: gated_short_conv(a, w)[0], wy)(bcx, taps)
    for i in range(2):
        alone = _every(lambda a, w: gated_short_conv(a, w)[0],
                       wy[i:i + 1])(bcx[i:i + 1], taps)
        for g, w in zip(both[:4], alone[:4]):
            _close(g[i:i + 1], w, 1e-6)
    as_one = gated_short_conv(bcx.reshape(1, 96, -1), taps)[0]
    assert float(jnp.max(jnp.abs(as_one.reshape(2, 48, -1)[1, :2]
                                 - both[0][1, :2]))) > 1e-2


def test_conv_from_a_tail_is_the_sequence_continued(form):
    """The cache's forms: a prefill that hands its tail on, then a chunk
    and single tokens from it, give the rows of the whole sequence; the
    tail is the last K - 1 rows of B * x."""
    bcx, taps, _ = _conv_case(2, 40, 128, 3)
    whole, last = gated_short_conv(bcx, taps)
    B, _, x = jnp.split(bcx, 3, axis=-1)
    _close(last, (B * x)[:, -2:], 1e-6)
    y0, tail = gated_short_conv(bcx[:, :17], taps)
    y1, tail = gated_short_conv(bcx[:, 17:30], taps, tail)
    rows = [y0, y1]
    for t in range(30, 40):
        y, tail = gated_short_conv(bcx[:, t:t + 1], taps, tail)
        rows.append(y)
    _close(jnp.concatenate(rows, axis=1), whole, 1e-6)
    _close(tail, last, 1e-6)
    # a sequence shorter than the taps still hands on K - 1 rows
    short, tail = gated_short_conv(bcx[:, :1], taps)
    assert tail.shape == (2, 2, 128) and not tail[:, 0].any()
    _close(short, whole[:, :1], 1e-6)


# ---------------------------------------------------------------------------
# the norm a head, rotary
# ---------------------------------------------------------------------------
def test_head_norm_is_a_norm_over_each_heads_columns():
    t = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 4 * 24))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (24,))
    want = reference.head_norm(t.reshape(2, 7, 4, 24), w, 1e-5)
    _close(head_rms_norm(t, w, 1e-5), want.reshape(t.shape), 1e-6)
    # not the norm over all columns (OLMoE's q_norm)
    whole = decoder.rms_norm(t, jnp.tile(w, 4), 1e-5)
    assert float(jnp.max(jnp.abs(whole - want.reshape(t.shape)))) > 1e-2


def test_rope_is_hfs_rotate_half_form():
    """ops.layers.rope turns the pair (t[i], t[i + hd/2]) by p / base^(2i
    / hd): HF's rotate_half form, not the interleaved one."""
    t = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))   # b s h hd
    want = reference._rotate_half(t, 1e6)
    got = rope(t.transpose(0, 2, 1, 3), base=1e6).transpose(0, 2, 1, 3)
    _close(got, want, 1e-6)


def test_attention_norms_each_head_then_rotates(tiny):
    cfg, params, (tok, _) = tiny
    layer = params["layers"][cfg.layer_types.index("full_attention")]
    layer = {**layer, "q_head_norm": layer["q_head_norm"] * 1.3,
             "k_head_norm": layer["k_head_norm"] * 0.7}
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 40, cfg.d_model))
    got, _ = decoder.attention(y, {k: v for k, v in layer.items()
                                   if k != "ln1"}, cfg.decoder())
    _close(got, reference._attention(y, layer, cfg))


# ---------------------------------------------------------------------------
# gated experts in the held-expert layer
# ---------------------------------------------------------------------------
def _expert_case(T=96, d=32, f=24, E=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, d)),
        router=jax.random.normal(ks[1], (d, E)) * d ** -0.5,
        bias=0.1 * jax.random.normal(ks[2], (E,)),
        gate_up=jax.random.normal(ks[3], (E, d, 2 * f)) * d ** -0.5,
        down=jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
        wy=jax.random.normal(ks[5], (T, d)))


def _held(c, first, count, bias=None, k=3, eps=1e-6, scale=1.0):
    return held_moe_layer(
        c["x"], c["router"], c["bias"] if bias is None else bias,
        c["gate_up"][first:first + count], c["down"][first:first + count],
        experts_per_token=k, first=first, routed_scale=scale, gated=True,
        weight_eps=eps)


def _plain(c, first, count, bias=None, k=3, eps=1e-6, scale=1.0):
    return reference._plain_experts(
        c["x"], c["router"], c["bias"] if bias is None else bias,
        c["gate_up"][first:first + count], c["down"][first:first + count],
        k=k, first=first, scale=scale, eps=eps)[0]


def test_gated_share_is_the_reference_with_every_gradient(form):
    c = _expert_case()

    def every(fn):
        def scalar(x, router, gate_up, down):
            given = {**c, "x": x, "router": router, "gate_up": gate_up,
                     "down": down}
            return jnp.sum(fn(given) * c["wy"])
        return jax.value_and_grad(scalar, argnums=(0, 1, 2, 3))(
            c["x"], c["router"], c["gate_up"], c["down"])

    want, dwant = every(lambda g: _plain(g, 2, 4))
    got, dgot = every(lambda g: _held(g, 2, 4)[0])
    _close(got, want)
    for g, w in zip(dgot, dwant):
        _close(g, w)


def test_the_two_shares_add_up_to_the_uncut_layer(form):
    """What ties the share to the model: experts 0-3 on one chip and 4-7
    on the other, each leaving out what the other's would add, sum to the
    reference's layer with all eight held (LFM2 has no shared expert to
    count once)."""
    c = _expert_case()
    uncut = _plain(c, 0, 8)
    lower, stats_lower = _held(c, 0, 4)
    upper, stats_upper = _held(c, 4, 4)
    _close(lower + upper, uncut)
    assert float(jnp.max(jnp.abs(lower))) > 1e-2 < float(
        jnp.max(jnp.abs(upper)))
    np.testing.assert_array_equal(stats_lower["expert_tokens"],
                                  stats_upper["expert_tokens"])
    assert int(stats_lower["expert_rows_held"]
               + stats_upper["expert_rows_held"]) == 96 * 3


@functools.lru_cache(maxsize=None)
def _skewed_case(T, E, favoured):
    """(the case, its bias, the rows it holds, the reference's output, its
    weighted sum and that sum's gradients), once for a routing: neither the
    kernels' form nor the way a pass's rows are added back is the
    reference's business."""
    k = 3
    c = _expert_case(T=T, E=E)
    if favoured is None:
        # one channel that the held experts' scores rise with and the
        # others' fall with, set high in the first half of the tokens and
        # low in the second
        mine = (np.arange(E) >= 1) & (np.arange(E) < 5)
        bias = jnp.zeros((E,))
        c["x"] = c["x"].at[:, 0].set(
            jnp.where(jnp.arange(T) < T // 2, 2.5, -2.5))
        c["router"] = c["router"].at[0].set(jnp.where(mine, 2.0, -2.0))
        rows_held = T // 2 * k
    else:
        bias = jnp.zeros((E,)).at[jnp.array(favoured)].set(10.0)
        rows_held = T * sum(1 <= e < 5 for e in favoured)
    with jax.default_matmul_precision("highest"):
        (want, plain), dwant = _with_every_gradient(
            c, lambda g: (_plain(g, 1, 4, bias),))
    return c, bias, rows_held, plain[0], want, dwant


def _with_every_gradient(c, fn):
    """((the weighted sum of fn's first output, all of its outputs), the
    sum's gradients by the tokens and both stacks of weights), as one
    program."""
    def scalar(x, gate_up, down):
        made = fn({**c, "x": x, "gate_up": gate_up, "down": down})
        return jnp.sum(made[0] * c["wy"]), made
    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2),
                                      has_aux=True))(
        c["x"], c["gate_up"], c["down"])


@pytest.mark.parametrize("tokens,experts,favoured,passes", [
    (512, 8, (5, 6, 7), 0), (512, 8, (2, 6, 7), 1), (512, 8, (1, 2, 3), 2),
    (512, 8, None, 1), (1200, 32, (1, 2, 3), 8)],
    ids=["none-held", "one-held", "all-held", "split", "all-held-of-32"])
def test_no_assignment_is_dropped_at_a_skewed_routing(form, back, tokens,
                                                      experts, favoured,
                                                      passes):
    """A bias no score outweighs puts every token on three experts: none
    of them held (no pass of the buffers), one held, or all three held
    (T * k rows where the buffers hold a balanced share and an eighth: two
    passes where 4 of 8 experts are held, eight where 4 of 32 are); or,
    split, the first half of the tokens have all three of theirs held and
    the second half none. The output and the gradients are the
    reference's at each, by either way of adding a pass's rows back."""
    T, E, k = tokens, experts, 3
    c, bias, rows_held, plain, want, dwant = _skewed_case(T, E, favoured)
    plan = held_rows_plan(T, k, 4, E)
    assert (plan.rows, plan.balanced) == {8: (1024, 768), 32: (512, 450)}[E]
    assert plan.gathered == (back == "gathered")

    (got, (out, stats)), dgot = _with_every_gradient(
        c, lambda g: _held(g, 1, 4, bias))
    assert int(stats["expert_rows_held"]) == rows_held
    assert int(stats["expert_passes"]) == passes
    _close(got, want)
    for g, w in zip(dgot, dwant):
        _close(g, w)
    _close(out, plain)
    if favoured is None:
        # the second half's rows are the reference's zeros, none of
        # another token's rows
        assert not np.asarray(out[T // 2:]).any()


def test_the_weights_small_number_is_the_callers():
    """w_j = s_j / (sum s + eps): 1e-6 is LFM2's, 1e-20 Nemotron-H's (the
    layer's default, which its cell's benchmark files call it by); one
    large enough to see shows it is the one used."""
    c = _expert_case()
    at = {eps: _held(c, 0, 8, eps=eps)[0] for eps in (1e-20, 1e-6, 1.0)}
    _close(at[1.0], _plain(c, 0, 8, eps=1.0))
    assert float(jnp.max(jnp.abs(at[1.0] - at[1e-6]))) > 1e-2
    _close(at[1e-6], at[1e-20], 1e-5)


def test_a_layer_with_no_shared_expert_adds_none():
    c = _expert_case()
    two = held_moe_layer(
        c["x"], c["router"], c["bias"], c["gate_up"][:, :, :24], c["down"],
        experts_per_token=3, first=0)[0]
    shared = held_moe_layer(
        c["x"], c["router"], c["bias"], c["gate_up"][:, :, :24], c["down"],
        c["gate_up"][0, :, 24:], c["down"][0], experts_per_token=3,
        first=0)[0]
    extra = jnp.square(jax.nn.relu(c["x"] @ c["gate_up"][0, :, 24:])) \
        @ c["down"][0]
    _close(shared - two, extra)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_layers_hold_what_the_config_names(tiny):
    cfg, params, _ = tiny
    assert cfg.decoder().kinds == (decoder.SHORT_CONV, decoder.SHORT_CONV,
                                   decoder.ATTENTION, decoder.SHORT_CONV)
    for i, (kind, layer) in enumerate(zip(cfg.layer_types,
                                          params["layers"])):
        assert ("conv_in" in layer) == (kind == "conv")
        assert ("q_head_norm" in layer) == (kind == "full_attention")
        assert ("w_gate" in layer) == (i < cfg.n_dense_layers)
        assert ("expert_gate_up" in layer) == (i >= cfg.n_dense_layers)
        assert "shared_up" not in layer and "q_norm" not in layer
    assert "head" not in params                     # the table is the head
    layer = params["layers"][1]
    assert layer["conv_in"].shape == (128, 384)
    assert layer["conv_taps"].shape == (3, 128)
    assert layer["expert_gate_up"].shape == (4, 128, 96)
    assert layer["router"].shape == (128, 8)
    axes = lfm2_moe_param_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    full = Lfm2MoeConfig.lfm2_8b_a1b()
    assert (full.n_layers, full.layer_types.count("conv"),
            full.layer_types.count("full_attention")) == (24, 18, 6)
    assert full.layer_types[:7] == ("conv", "conv", "full_attention", "conv",
                                    "conv", "conv", "full_attention")
    assert full.layer_types[19:] == ("conv", "conv", "full_attention",
                                     "conv", "conv")


def test_the_bias_starts_at_its_rules_fixed_point(tiny):
    """Layer by layer: an expert layer's bias is `balance_bias` of its own
    scores on the balancing tokens, the earlier layers already balanced."""
    cfg, params, _ = tiny
    zero = dataclasses.replace(cfg, balance_tokens=0)
    plain = lfm2_moe_init(jax.random.PRNGKey(0), zero)
    _, biases = split_bias(plain, cfg)
    assert not any(b.any() for b in biases)
    _, biases = split_bias(params, cfg)
    assert all(b.any() and abs(float(jnp.mean(b))) < 1e-6 for b in biases)
    # under them the seeded tokens fall evenly on the eight experts
    key = jax.random.split(jax.random.PRNGKey(0))[1]
    tokens = jax.random.randint(key, (1, cfg.balance_tokens), 0,
                                cfg.vocab_size)
    counters = lfm2_moe_loss_and_counters(
        params, (tokens, tokens), dataclasses.replace(cfg, bias_rounds=0))[1]
    counts = np.asarray(counters["expert_tokens"], np.float32)
    even = cfg.balance_tokens * cfg.experts_per_token / cfg.n_experts
    assert counts.shape == (3, 8) and np.abs(counts / even - 1).max() < 0.05


def test_logits_are_the_references(form, tiny):
    cfg, params, (tok, _) = tiny
    _close(lfm2_moe_forward(params, tok, cfg),
           reference.reference_logits(params, tok, cfg))


def test_loss_and_every_gradient_are_the_references(form, tiny,
                                                    reference_of_tiny):
    """A training step's forward: each bias first moved `bias_rounds`
    rounds on the batch's own scores, in the program and in the reference
    alike. No gradient reaches a bias."""
    cfg, params, batch = tiny
    want, dwant = reference_of_tiny
    got, dgot = jax.value_and_grad(
        lambda p: lfm2_moe_loss(p, batch, cfg))(params)
    _close(got, want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(dgot),
                            jax.tree.leaves(dwant)):
        if "router_bias" in jax.tree_util.keystr(path):
            assert not g.any() and not w.any()
        else:
            assert float(jnp.max(jnp.abs(w))) > 0, path
            _close(g, w)


def _jitted_loss(cfg, params, batch) -> float:
    # a function of its own: jit keeps what it traced for the loss
    return float(jax.jit(lambda p, b: lfm2_moe_loss(p, b, cfg))(params, batch))


@pytest.fixture(scope="module")
def loss_off_reference(tiny, reference_of_tiny):
    """How far the program's own loss lies from the reference's (at this
    size, in float32: a rounding or none)."""
    off = abs(_jitted_loss(*tiny) - float(reference_of_tiny[0]))
    assert off <= 1e-5
    return off


@pytest.mark.parametrize("fault", list(reference.STRUCTURAL_FAULTS))
def test_a_planted_fault_moves_the_loss_and_its_layer(
        tiny, reference_of_tiny, loss_off_reference, fault):
    """Each fault chipbench/limit_readings.py plants, here in-process (the
    cell's rehearsal plants two of the eight, tests/
    test_lfm2moe_cell_rehearsal.py): the model's loss leaves the
    reference's by ten times what the program's own is off at least (the
    bias added to the weights moves it least, 3.7e-4), the layers' own
    errors leave KERNEL_LIMIT far behind in some value, and `planted` puts
    the real layers back."""
    before = (decoder.gated_short_conv, decoder.head_rms_norm,
              decoder.held_moe_layer)
    with reference.planted(fault):
        wrong = _jitted_loss(*tiny)
        errors = reference.kernel_errors(tiny[0], seed=3, long=64)
    assert (decoder.gated_short_conv, decoder.head_rms_norm,
            decoder.held_moe_layer) == before
    off = abs(wrong - float(reference_of_tiny[0]))
    assert off > max(1e-4, 10 * loss_off_reference), (fault, wrong)
    assert np.isfinite(list(errors.values())).all()
    assert max(errors.values()) > 20 * reference.KERNEL_LIMIT, errors


def test_bfloat16_program_is_near_the_float32_reference():
    """The model's own dtype: bfloat16 weights and activations against the
    float32 reference on the same weights, loss and every gradient. Every
    token is given all eight experts here (k = E, no bias to move): with a
    choice to make, a score a rounding turns moves one of 80 tokens to
    another expert and an expert's gradient by half its largest value, at
    this size, in the all-bfloat16 reference as in the program, and the
    test would measure the seed."""
    cfg = _tiny(jnp.bfloat16, experts_per_token=8, bias_rounds=0,
                balance_tokens=0)
    params = lfm2_moe_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    want, dwant = jax.value_and_grad(
        lambda p: reference.reference_loss(p, *batch, cfg))(params)
    got, dgot = jax.value_and_grad(
        lambda p: lfm2_moe_loss(p, batch, cfg))(params)
    assert abs(float(got) - float(want)) <= TOL_BF16_LOSS
    for g, w in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(g - w))) <= TOL_BF16 * float(
            jnp.max(jnp.abs(w)))


def test_prefill_then_decode_is_the_full_forward(tiny):
    """Through the cache (a convolution layer's two rows of B * x, the
    attention layer's keys and values): a prefill of 33 tokens and 7
    single steps give the logits of the training forward and of the
    reference."""
    cfg, params, (tok, _) = tiny
    cache = init_cache(cfg, 2, 48)
    assert [sorted(layer) for layer in cache] == [
        ["conv"], ["conv"], ["k", "v"], ["conv"]]
    assert cache[0]["conv"].shape == (2, 2, 128)
    logits, cache = cached_forward(params, tok[:, :33], cache, 0, cfg)
    steps = [logits]
    for t in range(33, 40):
        logits, cache = cached_forward(params, tok[:, t:t + 1], cache, t,
                                       cfg)
        steps.append(logits)
    got = jnp.concatenate(steps, axis=1)
    _close(got, lfm2_moe_forward(params, tok, cfg))
    _close(got, reference.reference_logits(params, tok, cfg))


def test_train_step_keeps_the_biases_apart_and_carries_the_counters():
    cfg = _tiny()
    init_state, step = make_lfm2_moe_train_step(cfg, donate=False)
    state = init_state(jax.random.PRNGKey(0))
    params = lfm2_moe_init(jax.random.PRNGKey(0), cfg)
    assert state["held"].shape == (3, cfg.n_experts)
    assert not any("router_bias" in layer
                   for layer in state["params"]["layers"])
    np.testing.assert_array_equal(state["held"],
                                  jnp.stack(split_bias(params, cfg)[1]))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    new, metrics = step(state, batch)
    assert {"loss", "expert_tokens", "expert_rows_held", "expert_passes",
            "router_bias", "router_prob_sum", "expert_load_max_over_mean",
            "router_bias_abs_max"} <= set(metrics)
    assert metrics["expert_tokens"].shape == (3, cfg.n_experts)
    assert metrics["expert_rows_held"].shape == (3,)
    np.testing.assert_array_equal(metrics["expert_passes"], [1, 1, 1])
    assert np.asarray(metrics["expert_tokens"]).sum(-1).tolist() == [240] * 3
    # the step keeps what the loss's forward came to, and that is the
    # rule's rounds on the first expert layer's own scores
    np.testing.assert_array_equal(new["held"], metrics["router_bias"])
    layer, dec = params["layers"][1], cfg.decoder()
    x = jnp.take(params["embed"], tok, axis=0)
    x = decoder._block(x, params["layers"][0], None, None, dec=dec,
                       kind=dec.kinds[0], mlp=dec.mlp[0])[0]
    x = x + decoder.short_conv(x, layer, dec)[0]
    scores = router_scores(decoder.rms_norm(
        x, layer["ln2"], cfg.norm_eps).reshape(-1, cfg.d_model),
        layer["router"])
    want = balance_bias(scores, cfg.experts_per_token, cfg.bias_rounds,
                        layer["router_bias"])
    np.testing.assert_allclose(new["held"][0], want, atol=1e-6)
    losses = [float(metrics["loss"])]
    for _ in range(3):
        new, metrics = step(new, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_with_bias_undoes_split_bias(tiny):
    cfg, params, _ = tiny
    rest, biases = split_bias(params, cfg)
    back = with_bias(rest, jnp.stack(biases), cfg)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the benchmark's counts, by hand
# ---------------------------------------------------------------------------
def test_counts_are_the_hand_computed_ones():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs/lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    cfg = reference.build(config, remat=True)
    d, S, T = 2048, 8192, 32768
    conv = 2 * d * 3 * d + 2 * d * d + 8 * d
    attention = 2 * d * d + 2 * d * 1024 + 2 * d * d + 2 * S * d
    dense = 6 * d * 7168
    experts = 2 * d * 32 + 2 * 6 * d * 1792
    forward = 4 * conv + attention + dense + 4 * experts + 2 * d * 32768
    for given in (config, cfg):
        assert reference.forward_flops_per_token(given, S) == forward
        assert reference.train_flops_per_token(given, S) == 3 * forward
        assert reference.held_rows_balanced(given, T) == 65536
        assert reference.expert_matmul_flops(given, T) == (
            4 * 9 * 2 * 65536 * d * 1792)
        assert reference.expert_matmul_bytes(given, T) == 4 * 9 * 2 * (
            65536 * (d + 1792) + 16 * d * 1792)
        assert reference.attention_kernel_flops(given, 4, S) == (
            6 * 2 * 4 * S * S * d / 2)
        assert reference.attention_kernel_bytes(given, 4, S) == (
            6 * 4 * S * d * 2 + 6 * 4 * S * 512 * 2)
        assert reference.short_conv_bytes(given, 4, S) == 4 * T * d * 11 * 2
        assert reference.short_conv_flops(given, 4, S) == 4 * T * d * 30
    assert cfg.held == (0, 16) and cfg.n_experts == 32
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv") and cfg.n_dense_layers == 1
    shapes = jax.eval_shape(
        dataclasses.replace(cfg, balance_tokens=0).init,
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 893_696_256
