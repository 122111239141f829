"""models.bailing_hybrid (Ling-3.0-flash: Kimi Delta Attention, a delta
rule whose decay is one number a key channel, in five layers of six beside
a gated latent-attention layer; a dense SwiGLU layer and then a held share
of SwiGLU experts behind a sigmoid router limited to some of its groups,
beside a shared expert; an untied head) against the benchmark's plain
float32 reference (chipbench/families/bailing_hybrid.py) on seeded weights,
and the pieces this family brought: ops/kda.py's kernel pair, the
fifteenth kind of models/decoder.py's MIXERS, the latent layer's gate and
the group limit in parallel/moe.py's one choosing function."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import bailing_hybrid as reference
from ray_tpu.models import bailing_hybrid as program
from ray_tpu.models.bailing_hybrid import BailingHybridConfig
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.ops import gated_delta, kda
from ray_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "chipbench/configs/ling-3.0-flash.json"
# float32 program against float32 reference: the same sums in another order
# (chunks and a reference row against one token after another; a latent and
# one product more; sorted rows against every expert on every token).
TOL = 1e-4


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


def _drawn_apart(params, key=7):
    """`params` with every norm's weight, A_log and dt_bias off their
    start: at ones and zeros a left-out norm or rate would not show."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(key), len(leaves))
    out = []
    for k, (path, a) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name or a.ndim != 1:
            out.append(a)
        elif "A_log" in name or "dt_bias" in name:
            out.append(0.3 * jax.random.normal(k, a.shape))
        else:
            out.append(1.0 + 0.2 * jax.random.normal(k, a.shape))
    return jax.tree.unflatten(tree, out)


@pytest.fixture(scope="module")
def tiny():
    """(the tiny config in float32 with KDA heads of 32, which run the
    jax.numpy rule here; its seeded weights drawn apart; a batch of two
    48-token sequences)."""
    cfg = dataclasses.replace(BailingHybridConfig.tiny(), dtype=jnp.float32,
                              kda_head_dim=32, kda_chunk=16, bias_rounds=8)
    params = _drawn_apart(program.bailing_hybrid_init(
        jax.random.PRNGKey(0), cfg))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                             cfg.vocab_size)
    return cfg, params, (tok, jnp.roll(tok, -1, 1))


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------
def test_logits_loss_and_every_gradient_are_the_references(tiny):
    cfg, params, batch = tiny
    assert cfg.kinds == ("kda", "kda", "latent_attention")
    _close(jax.jit(lambda p: program.bailing_hybrid_forward(
        p, batch[0], cfg))(params),
        jax.jit(lambda p: reference.reference_logits(p, batch[0], cfg))(
            params))
    want, dwant = jax.jit(jax.value_and_grad(
        lambda p: reference.reference_loss(p, *batch, cfg)))(params)
    got, dgot = jax.jit(jax.value_and_grad(
        lambda p: program.bailing_hybrid_loss(p, batch, cfg)))(params)
    assert abs(float(got) - float(want)) <= TOL * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(dwant))
    seen = 0
    for path, g in jax.tree_util.tree_leaves_with_path(dgot):
        if "router_bias" in jax.tree_util.keystr(path):
            continue                        # no gradient reaches it
        assert float(jnp.max(jnp.abs(g))) > 0, path
        _close(g, flat_want[path])
        seen += 1
    # every leaf: two KDA layers' ten, the latent layer's six, six block
    # norms, a dense layer's three, two expert layers' five, three outside
    assert seen == 2 * 9 + 6 + 6 + 3 + 2 * 5 + 3


def test_prefill_then_decode_through_both_caches_is_the_full_forward(tiny):
    cfg, params, batch = tiny
    tok = batch[0]
    want = jax.jit(lambda p: reference.reference_logits(
        p, tok[:, :43], cfg))(params)
    cache = init_cache(cfg, 2, 64)
    assert [sorted(layer) for layer in cache] == [
        ["conv", "kda"], ["conv", "kda"], ["k_rope", "latent"]]
    assert cache[0]["kda"].shape == (2, cfg.n_heads, 32, 32)
    assert cache[0]["kda"].dtype == jnp.float32
    # a prefill of 40 (no whole number of chunks of 16), then three tokens
    forward = jax.jit(lambda p, toks, cache, at: cached_forward(
        p, toks, cache, at, cfg))
    got, cache = forward(params, tok[:, :40], cache, 0)
    for i in range(40, 43):
        step, cache = forward(params, tok[:, i:i + 1], cache, i)
        got = jnp.concatenate([got, step], axis=1)
    _close(got, want)


def test_train_step_keeps_the_biases_and_reports_the_counters(tiny):
    cfg, _, batch = tiny
    init_state, step = program.make_bailing_hybrid_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    assert state["held"].shape == (2, cfg.n_experts)
    state, m = step(state, batch)
    tokens = batch[0].size
    assert m["expert_groups_kept"].shape == (2, cfg.n_group)
    assert (np.asarray(m["expert_groups_kept"]).sum(-1)
            == cfg.topk_group * tokens).all()
    assert m["kda_log_decay_min"].shape == (2,)
    assert (np.asarray(m["kda_log_decay_min"]) > cfg.kda_lower_bound).all()
    assert m["expert_tokens"].shape == (2, cfg.n_experts)
    assert (np.asarray(m["expert_tokens"]).sum(-1)
            == cfg.experts_per_token * tokens).all()
    np.testing.assert_array_equal(state["held"], m["router_bias"])
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def _rule_inputs(L, H=2, K=128, with_state=False, seed=0, bound=-5.0,
                 decay="drawn"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, L, H, K))
    k = jax.random.normal(ks[1], (1, L, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, L, H, K))
    # drawn down to the bound: some channel of every sub-block sits at it
    g = bound * jax.random.uniform(ks[3], (1, L, H, K))
    g = g.at[:, :, :, 0].set(bound)
    if decay == "bound":        # every row and channel: G ends a chunk at
        g = jnp.full_like(g, bound)                     # 64 * bound
    elif decay == "near_zero":  # every row and channel within 1e-3 of 0
        g = g * (1e-3 / -bound)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, L, H)))
    init = 0.5 * jax.random.normal(ks[5], (1, H, K, K)) if with_state \
        else None
    return q, k, v, g, beta, init


def _weighted(fn, *given):
    o, S = fn(*given)
    return (jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
            + jnp.sum(S * jnp.sin(jnp.arange(S.size).reshape(S.shape))))


@pytest.mark.parametrize("chunks, with_state, decay", [
    (2, False, "drawn"), (3, True, "drawn"),
    (2, True, "bound"), (2, False, "near_zero")])
def test_kernels_are_the_recurrence_with_g_down_to_the_bound(
        monkeypatch, chunks, with_state, decay):
    """The interpreted kernel pair against the family's token-by-token
    recurrence: o, the final state and every gradient. The kernels make
    the running sums of g themselves: with g AT the bound on every row and
    channel a chunk's last sum is -320, where a sum (or a g) rounded once
    to bfloat16 is off by more than 1; with g within 1e-3 of 0 everywhere
    the sums are all the decay there is."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    given = _rule_inputs(64 * chunks, with_state=with_state, decay=decay)
    n = 6 if with_state else 5

    def ours(*a):
        return kda.kda_rule(*a[:5], 64, a[5] if with_state else None)

    def theirs(*a):
        return reference.recurrence(*a[:5], a[5] if with_state else None)

    for got, want in zip(jax.jit(ours)(*given[:n], None),
                         jax.jit(theirs)(*given[:n], None)):
        _close(got, want)
    dgot = jax.jit(jax.grad(lambda *a: _weighted(ours, *a, None),
                            argnums=range(n)))(*given[:n])
    dwant = jax.jit(jax.grad(lambda *a: _weighted(theirs, *a, None),
                             argnums=range(n)))(*given[:n])
    for got, want in zip(dgot, dwant):
        _close(got, want)


@pytest.mark.parametrize("decay", ["drawn", "near_zero"])
def test_a_bias_on_every_tokens_g_has_the_recurrences_gradient(
        monkeypatch, decay):
    """The gradient the gate's bias takes (PR 63's finding (a)): one number
    a channel added to g on every token, so its gradient sums dg over the
    whole sequence, and what a chunk's sum should cancel has to cancel. dg
    is the backward kernel's gradient by G summed from each row to its
    chunk's end, inside the kernel: a gradient or a sum rounded to
    bfloat16 there fails here, before `hold_kernels` meets it on the
    chip."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, init = _rule_inputs(192, with_state=True, seed=3,
                                          decay=decay)
    H, K = g.shape[2:]

    def biased(rule):
        return jax.jit(jax.grad(lambda bias: _weighted(
            rule, q, k, v, g + bias, beta, init)))(jnp.zeros((H, K)))

    got = biased(lambda *a: kda.kda_rule(*a[:5], 64, a[5]))
    want = biased(reference.recurrence)
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(got, want)
    # and the kernels' sums themselves, both ways, against float64's: 64
    # float32 additions of values up to 320 in size, nothing rounded shorter
    from jax.experimental import pallas as pl

    def both_ways(x_ref, down_ref, up_ref):
        down_ref[...] = kda._running_sums(x_ref[...])
        up_ref[...] = kda._running_sums(x_ref[...], to_end=True)

    x = g[0, :64].reshape(64, -1)
    down, up = pl.pallas_call(
        both_ways, out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] * 2,
        interpret=True)(x)
    x = np.asarray(x, np.float64)
    for ours, exact in ((down, np.cumsum(x, 0)),
                        (up, np.cumsum(x[::-1], 0)[::-1])):
        assert np.max(np.abs(np.asarray(ours, np.float64) - exact)) \
            <= 64 * 2.0 ** -24 * 320


def test_a_length_that_is_no_whole_number_of_chunks_runs_the_reference(
        monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, init = _rule_inputs(100, K=32, with_state=True)
    got = kda.kda_rule(q, k, v, g, beta, 64, init)
    for ours, theirs in zip(got, reference.recurrence(q, k, v, g, beta,
                                                      init)):
        _close(ours, theirs)
    # and the family's own chunked form is a second reading of the same
    for ours, theirs in zip(
            reference.chunked(*(t[:, :96] for t in (q, k, v, g, beta)), init,
                              32),
            reference.recurrence(*(t[:, :96] for t in (q, k, v, g, beta)),
                                 init)):
        _close(ours, theirs)


def test_one_decay_a_head_is_the_gated_delta_rule(monkeypatch):
    """A decay that is the same in every channel of a head: both kernel
    pairs, interpreted, on the same inputs."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, init = _rule_inputs(128, with_state=True)
    a_head = g[..., 0] * jax.random.uniform(jax.random.PRNGKey(9),
                                            g.shape[:-1])
    got = kda.kda_rule(q, k, v, jnp.broadcast_to(a_head[..., None], g.shape),
                       beta, 64, init)
    want = gated_delta.gated_delta_rule(q, k, v, a_head, beta, 64, init)
    for ours, theirs in zip(got, want):
        _close(ours, theirs)


def test_plan_counts_and_refuses_a_bound_the_sub_blocks_do_not_hold():
    plan = kda.kda_plan(16384, 32, 128, 128, 64)
    assert (plan.chunks, plan.sub_blocks, plan.heads_per_program,
            plan.grid) == (256, 4, 8, (4, 256))
    assert plan.inverse_matmuls == 256 * 16 * 2 * 5 == 40_960
    # (the running sums of g are the vector unit's: no product is added)
    assert plan.fwd_matmuls == 40_960 + 256 * 32 * 7 == 98_304
    assert plan.bwd_matmuls == 256 * 32 * 19 == 155_648
    assert plan.fwd_exps == plan.bwd_exps == 256 * 32 * 7 == 57_344
    assert plan.state_bytes == 256 * 32 * 128 * 128 * 2 == 268_435_456
    assert plan.kept_bytes == 256 * 32 * 64 * 64 * 2 == 67_108_864
    assert plan.decay_bytes == 16384 * 4096 * 4 == 268_435_456
    assert plan.vmem_bytes <= kda.VMEM_LIMIT
    kda.kda_plan(16384, 32, 128, 128, 64, lower_bound=-5.5)
    with pytest.raises(ValueError, match="not clamped"):
        kda.kda_plan(16384, 32, 128, 128, 64, lower_bound=-5.6)
    with pytest.raises(ValueError, match="whole chunks"):
        kda.kda_plan(100, 32, 128, 128, 64)


# ---------------------------------------------------------------------------
# the group limit
# ---------------------------------------------------------------------------
def _expert_layer(E=64, held=None, d=32, f=16, T=96, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    held = held or E
    return dict(
        x=jax.random.normal(ks[0], (T, d)),
        router=jax.random.normal(ks[1], (d, E)) * d ** -0.5,
        bias=0.1 * jax.random.normal(ks[2], (E,)),
        gate_up=jax.random.normal(ks[3], (E, d, 2 * f)) * d ** -0.5,
        down=jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
        shared_gate_up=jax.random.normal(ks[5], (d, 2 * f)) * d ** -0.5,
        shared_down=jax.random.normal(ks[6], (f, d)) * f ** -0.5)


def _held(w, first, count, shared=True, **sizes):
    return moe.held_moe_layer(
        w["x"], w["router"], w["bias"],
        w["gate_up"][first:first + count], w["down"][first:first + count],
        w["shared_gate_up"] if shared else None,
        w["shared_down"] if shared else None, experts_per_token=8,
        first=first, routed_scale=2.5, gated=True, **sizes)


def test_the_32_shares_add_up_to_the_uncut_layer_under_the_group_limit():
    """64 experts in 8 groups, 4 kept, 8 a token; 32 chips hold two each, a
    quarter of a group as published. The group choice is made over all the
    experts alike on every chip, so the shares' routed parts sum to the
    uncut layer's, the shared expert counted once; and the whole is the
    reference's."""
    w = _expert_layer()
    limit = dict(n_group=8, topk_group=4)
    whole, stats = _held(w, 0, 64, **limit)
    shared_once = whole - _held(w, 0, 64, shared=False, **limit)[0]
    parts = [_held(w, first, 2, shared=False, **limit)
             for first in range(0, 64, 2)]
    _close(sum(out for out, _ in parts) + shared_once, whole)
    rows = [int(s["expert_rows_held"]) for _, s in parts]
    assert sum(rows) == 8 * 96 and max(rows) > 0
    for _, s in parts:          # every chip saw the same routing
        np.testing.assert_array_equal(s["expert_tokens"],
                                      stats["expert_tokens"])
        np.testing.assert_array_equal(s["expert_groups_kept"],
                                      stats["expert_groups_kept"])
    assert int(stats["expert_groups_kept"].sum()) == 4 * 96
    want, chosen = reference._plain_experts(
        w["x"], w["router"], w["bias"], w["gate_up"], w["down"],
        w["shared_gate_up"], w["shared_down"], k=8, first=0, scale=2.5,
        n_group=8, topk_group=4)
    _close(whole, want)
    # no token went outside its four groups, and the limit binds: without
    # it some token's eight lie in five groups or more
    groups = np.asarray(chosen) // 8
    assert max(len(set(row)) for row in groups) <= 4
    free = np.asarray(jax.lax.top_k(
        jax.nn.sigmoid(w["x"] @ w["router"]) + w["bias"], 8)[1]) // 8
    assert max(len(set(row)) for row in free) > 4


def test_a_groups_mark_is_the_sum_of_its_two_best():
    biased = jnp.array([[0.9, 0.0, 0.5, 0.5, 0.3, 0.1, 0.6, 0.2]])
    # groups of two: marks 0.9, 1.0, 0.4, 0.8 -> groups 1 and 0 kept
    narrowed, keep = moe.within_groups(biased, 4, 2)
    np.testing.assert_array_equal(keep, [[True, True, False, False]])
    np.testing.assert_array_equal(
        np.isfinite(narrowed), [[1, 1, 1, 1, 0, 0, 0, 0]])
    np.testing.assert_array_equal(
        reference._narrowed(biased, 4, 2), narrowed)
    # by its best one alone group 3 (0.6) would beat group 1 (0.5)


def test_one_group_chooses_what_the_function_chose_before(monkeypatch):
    """`n_group` 1 adds no operation: the layer and the bias's rounds trace
    to the text they trace to with the narrowing taken out."""
    w = _expert_layer(E=16, T=32)
    biased = w["x"] @ w["router"]
    assert moe.within_groups(biased) == (biased, None)
    assert moe.within_groups(biased, 1, 1)[0] is biased

    def traced(**sizes):
        return str(jax.make_jaxpr(lambda x: moe.held_moe_layer(
            x, w["router"], w["bias"], w["gate_up"][:4], w["down"][:4],
            experts_per_token=3, first=2, gated=True, bias_rounds=8,
            **sizes))(w["x"]))

    ours, one = traced(), traced(n_group=1, topk_group=1)
    monkeypatch.setattr(moe, "within_groups", lambda b, *a: (b, None))
    assert ours == one == traced()
    assert "expert_groups_kept" not in _held(w, 0, 16)[1]


def test_the_bias_balances_under_the_group_limit():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3),
                                              (2048, 64)))
    bias = moe.balance_bias(scores, 8, 256, None, 8, 4)
    counts = np.bincount(np.asarray(jax.lax.top_k(
        moe.within_groups(scores + bias, 8, 4)[0], 8)[1]).ravel(),
        minlength=64)
    assert counts.max() / counts.mean() < 1.1
    _close(bias, reference._bias_moved(scores, jnp.zeros(64), 8, 256, 8, 4),
           1e-3)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------
def test_counts_are_the_hand_computed_ones():
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    cfg = reference.build(config)
    shapes = jax.eval_shape(
        lambda: program._weights(jax.random.PRNGKey(0), cfg))
    layers = [sum(a.size for a in jax.tree.leaves(
        {k: v for k, v in layer.items() if k != "router_bias"}))
        for layer in shapes["layers"]]
    kda_mixer = 5 * 10_485_760 + 49_152 + 2 * 81_920 + 32 + 4_096 + 128
    latent_mixer = (15_728_640 + 1_474_560 + 512 + 4_194_304 + 10_485_760
                    + 81_920)
    experts = 1_310_720 + 16 * 5_898_240 + 5_898_240
    assert (kda_mixer, latent_mixer) == (52_646_048, 31_965_696)
    assert layers == [kda_mixer + 5_120 + 47_185_920,
                      *[kda_mixer + 5_120 + experts] * 4,
                      latent_mixer + 5_120 + experts]
    assert layers[0] == 99_837_088 and layers[1] == 154_231_968 \
        and layers[5] == 133_551_616
    total = sum(layers) + 2 * 19_648 * 2_560 + 2_560
    assert total == 950_916_896
    assert cfg.kinds == ("kda",) * 5 + ("latent_attention",)
    # operations: the config's dict and the program's object count alike
    for counts in (reference.train_flops_per_token, ):
        assert counts(config, 16384) == counts(cfg, 16384)
    s = reference._dims(config)
    chunk_form = 32 * (2 * 2 * 128 * 32.5 * 2 + 3 * 2 * 128 * 128
                       + 2 * 128 * 32.5)
    assert reference._chunk_flops_per_token(s) == chunk_form == 4_476_928
    assert reference.kda_flops(config, 1, 16384) \
        == 5 * 3 * 16384 * chunk_form
    assert reference.kda_bytes(config, 1, 16384) == 5 * (
        16384 * (8 * 4096 * 2 + 2 * 4096 * 4 + 2 * 32 * 4)
        + 2 * 256 * 32 * 128 * 128 * 4)
    assert reference.attention_kernel_flops(config, 1, 16384) \
        == 1 * 2 * 16384 ** 2 * 32 * 3 * 320 / 2
    assert reference.expert_matmul_flops(config, 16384) \
        == 5 * 9 * 2 * 4096 * 2560 * 768
    assert reference.held_rows_balanced(config, 16384) == 4096
    assert 3.1e9 < reference.train_flops_per_token(config, 16384) < 3.3e9


# ---------------------------------------------------------------------------
# the planted faults
# ---------------------------------------------------------------------------
def test_every_planted_fault_moves_the_group_it_names(tiny):
    """`planted` swaps a name on a module of the program and the group of
    `kernel_errors` it names is traced through it: in float32 the program
    is the reference to 1e-5, and each of the fifteen faults is a
    hundredth of a value's size or more away in its own group (on the chip
    this runs at the published sizes against KERNEL_LIMIT:
    chipbench/limit_readings.py)."""
    cfg = tiny[0]
    clean = reference.held(reference.kernel_errors(cfg, 0))
    assert set(k.split("_")[0] for k in clean) == set(reference.GROUPS)
    assert max(clean.values()) < 1e-5
    assert len(reference.STRUCTURAL_FAULTS) == 15
    for fault, (_, _, _, group) in reference.STRUCTURAL_FAULTS.items():
        with reference.planted(fault):
            moved = reference.held(reference.kernel_errors(cfg, 0))
        assert set(k.split("_")[0] for k in moved) == {group}, fault
        assert max(moved.values()) > 1e-2, (fault, moved)
    # and nothing stays swapped
    again = reference.held(reference.kernel_errors(cfg, 0, groups=("rule",)))
    assert again["rule_mean"] == clean["rule_mean"]
