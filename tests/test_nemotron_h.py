"""models.nemotron_h (Nemotron-3-Nano style: one branch a layer, Mamba-2 in
groups, a held share of relu^2 experts behind a sigmoid router with a
selection bias, a shared expert) against the benchmark's plain float32
reference (chipbench/families/nemotron_h.py) on seeded weights, and the
pieces this family brought to ops/ and parallel/: the scan kernels with
groups, the gated norm a group, grouped matmuls whose sizes sum to less
than their rows, the held-expert layer, the bias's two rules. Both forms
where a kernel is involved: jax.numpy (what the CPU runs) and the Pallas
kernels interpreted."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import nemotron_h as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.nemotron_h import (NemotronHConfig,
                                       make_nemotron_h_train_step,
                                       nemotron_h_forward, nemotron_h_init,
                                       nemotron_h_loss,
                                       nemotron_h_loss_and_counters,
                                       nemotron_h_param_axes, split_bias)
from ray_tpu.ops.grouped_matmul import (grouped_matmul, grouped_matmul_grads,
                                        past_groups_zeroed)
from ray_tpu.ops.layers import gated_rms_norm, rms_norm
from ray_tpu.ops.ssm_scan import ssm_scan, ssm_scan_plan
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import (balance_bias, held_moe_layer,
                                  held_rows_plan, router_scores)

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


@pytest.fixture(params=["gathered", "scattered"])
def back(request, monkeypatch):
    """Both ways a pass's rows are added back to their tokens, at shapes
    whose plan would name one: the plan's bound moved past every shape, or
    under all (`held_rows_plan`; parallel/moe.py `_gathered_back`)."""
    monkeypatch.setattr(moe, "_GATHERED_BACK_UP_TO",
                        {"gathered": 10 ** 9, "scattered": 0}[request.param])
    return request.param


@pytest.fixture(scope="module")
def tiny():
    """(config in float32, its seeded weights with the balanced biases, a
    batch of two 32-token sequences)."""
    cfg = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32)
    params = nemotron_h_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
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
# the scan kernels with groups, the gated norm a group
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("H,G,L,chunk,P,N", [
    (16, 8, 32, 8, 8, 16),        # two heads a group: a block is a pair
    (64, 8, 256, 128, 8, 16),     # the model's 64 heads in 8 groups, chunks
                                  # of 128: a block IS a group
    (32, 2, 32, 8, 8, 16),        # two blocks of 8 heads share a group
], ids=["pairs", "published-groups-and-chunk", "blocks-share-a-group"])
def test_grouped_scan_kernels_equal_the_recurrence_with_every_gradient(
        monkeypatch, H, G, L, chunk, P, N):
    """The kernels, interpreted (the jax form took any number of groups
    before them, tests/test_granite_hybrid.py)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(H + G), 8)
    b = 2
    args = (jax.random.normal(ks[0], (b, L, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, L, H))),
            -jnp.exp(jnp.linspace(jnp.log(1e-2), 0.0, H)),
            jax.random.normal(ks[2], (b, L, G, N)),
            jax.random.normal(ks[3], (b, L, G, N)),
            jax.random.normal(ks[4], (H,)),
            jax.random.normal(ks[5], (b, H, P, N)))
    wy, ws = (jax.random.normal(ks[6], (b, L, H, P)),
              jax.random.normal(ks[7], (b, H, P, N)))

    def every(fn):
        def scalar(*given):
            y, state = fn(*given)
            return jnp.sum(y * wy) + jnp.sum(state * ws), (y, state)
        return jax.value_and_grad(scalar, argnums=tuple(range(7)),
                                  has_aux=True)

    (_, (y0, s0)), g0 = every(reference.recurrence)(*args)
    (_, (y1, s1)), g1 = every(
        lambda *t: ssm_scan(*t[:6], chunk, t[6]))(*args)
    _close(y1, y0)
    _close(s1, s0)
    for got, want in zip(g1, g0):
        _close(got, want)
    text = str(jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        ssm_scan(x, *args[1:6], chunk, args[6])[0])))(args[0]))
    assert text.count("pallas_call") == 2


def test_scan_plan_keeps_a_block_inside_a_group():
    plan = ssm_scan_plan(16384, 64, 64, 128, 128, groups=8)
    assert (plan.chunks, plan.heads_per_block, plan.grid, plan.groups) == (
        128, 8, (128, 8), 8)
    assert ssm_scan_plan(16384, 64, 64, 128, 128, groups=16
                         ).heads_per_block == 4
    assert ssm_scan_plan(16384, 64, 64, 128, 256).groups == 1
    with pytest.raises(ValueError, match="inside a group"):
        ssm_scan_plan(1024, 24, 64, 128, 128, groups=8)   # 3 heads a group


def test_gated_norm_norms_each_group_alone():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y, z = (jax.random.normal(k, (2, 5, 64)) for k in ks[:2])
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (64,))
    got = gated_rms_norm(y, z, w, 1e-5, groups=8)
    _close(got, reference._gated_group_norm(y, z, w, 8, 1e-5), 1e-6)
    g = (y * jax.nn.silu(z)).reshape(2, 5, 8, 8)
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
    _close(got, want.reshape(2, 5, 64) * w, 1e-6)
    # one group is the one norm over all channels, as before
    _close(gated_rms_norm(y, z, w, 1e-5),
           reference._gated_group_norm(y, z, w, 1, 1e-5), 1e-6)
    assert not np.allclose(got, gated_rms_norm(y, z, w, 1e-5), atol=1e-2)


def _group_norm_by_reshape(y, gate, weight, groups, eps):
    """The plain form, in float32: a groups axis, a mean, a root."""
    f32 = jnp.float32
    g = (y.astype(f32) * jax.nn.silu(gate.astype(f32))).reshape(
        *y.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(y.shape) * weight.astype(f32)


def _norm_inputs(groups, run, dtype):
    ks = jax.random.split(jax.random.PRNGKey(groups * 1000 + run), 4)
    shape = (2, 24, groups * run)
    y, gate, cot = (jax.random.normal(k, shape).astype(dtype)
                    for k in ks[:3])
    return y, gate, 1.0 + 0.1 * jax.random.normal(ks[3], shape[-1:]), cot


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("run", [32, 96, 512])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_gated_norm_against_the_reshaped_form(groups, run, dtype):
    """The value and the gradients by y, gate and weight: float32 inputs
    to 1e-5 of the largest value, bfloat16 inputs (the weight stays
    float32, as a model's is) to one bfloat16 rounding of each value."""
    y, gate, weight, cot = _norm_inputs(groups, run, dtype)
    f32 = jnp.float32

    def every(fn, *given):
        out, vjp = jax.vjp(fn, *given)
        return (out,) + vjp(cot.astype(out.dtype))

    got = every(lambda *t: gated_rms_norm(*t, 1e-5, groups), y, gate, weight)
    want = every(lambda *t: _group_norm_by_reshape(*t, groups, 1e-5),
                 y.astype(f32), gate.astype(f32), weight)
    assert [t.dtype for t in got] == [dtype, dtype, dtype, f32]
    for a, b in zip(got, want):
        b = np.asarray(b)
        rounding = 2.0 ** -8 * np.abs(b) if a.dtype == jnp.bfloat16 else 0.0
        np.testing.assert_array_less(
            np.abs(np.asarray(a.astype(f32)) - b),
            rounding + 1e-5 * np.abs(b).max())


@pytest.mark.parametrize("width", [32, 96, 512])
def test_gated_norm_of_one_group_is_rms_norm_to_the_bit(width):
    y, gate, weight, _ = _norm_inputs(1, width, jnp.bfloat16)
    gated = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(gated_rms_norm(y, gate, weight, 1e-5).astype(jnp.float32)),
        np.asarray(rms_norm(gated, weight, 1e-5).astype(y.dtype)
                   .astype(jnp.float32)))


# ---------------------------------------------------------------------------
# grouped matmuls whose sizes sum to less than their rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [(40, 0, 25, 31), (0, 0, 0, 0),
                                   (128, 0, 0, 0), (0, 0, 0, 7)])
def test_grouped_matmul_with_rows_past_the_last_group(form, sizes):
    m, k, n = 256, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes)), 3)
    lhs = jax.random.normal(ks[0], (m, k))
    rhs = jax.random.normal(ks[1], (len(sizes), k, n))
    g = jax.random.normal(ks[2], (m, n))
    sizes = jnp.array(sizes, jnp.int32)
    group = np.repeat(np.arange(len(sizes)), np.asarray(sizes))
    total = len(group)
    want = np.zeros((m, n), np.float32)
    want[:total] = np.einsum("mk,mkn->mn", lhs[:total], rhs[group])
    out = past_groups_zeroed(grouped_matmul(lhs, rhs, sizes), sizes)
    _close(out, want)
    assert not out[total:].any()
    dlhs, drhs = grouped_matmul_grads(lhs, rhs, sizes, g)
    _close(past_groups_zeroed(dlhs, sizes)[:total],
           np.einsum("mn,mkn->mk", g[:total], rhs[group]))
    want_drhs = np.zeros(rhs.shape, np.float32)
    for e in range(len(sizes)):
        rows = np.flatnonzero(group == e)
        want_drhs[e] = np.asarray(lhs)[rows].T @ np.asarray(g)[rows]
    _close(drhs, want_drhs)       # rows past the sum count for no group


# ---------------------------------------------------------------------------
# the held share of an expert layer
# ---------------------------------------------------------------------------
def _layer_weights(seed=0, T=64, d=32, E=16, held=4, f=24, fs=40):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (T, d)),
        router=jax.random.normal(ks[1], (d, E)) * d ** -0.5,
        up=jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
        down=jax.random.normal(ks[3], (E, f, d)) * f ** -0.5,
        s_up=jax.random.normal(ks[4], (d, fs)) * d ** -0.5,
        s_down=jax.random.normal(ks[5], (fs, d)) * fs ** -0.5,
        bias=0.1 * jax.random.normal(ks[6], (E,)))


def _share(w, first, held, bias=None, k=3, **kw):
    bias = w["bias"] if bias is None else bias
    return held_moe_layer(
        w["x"], w["router"], bias, w["up"][first:first + held],
        w["down"][first:first + held], w["s_up"], w["s_down"],
        experts_per_token=k, first=first, routed_scale=2.5, **kw)


def test_the_eight_shares_add_up_to_the_uncut_layer(form):
    """What ties the share to the model: the parts that all eight chips'
    shares give, with the shared expert (what every chip computes alike)
    counted once, are the uncut reference layer."""
    w = _layer_weights(E=16)
    whole, chosen, _ = reference._plain_experts(
        w["x"], w["router"], w["bias"], w["up"], w["down"], w["s_up"],
        w["s_down"], k=3, first=0, scale=2.5)
    shared = jnp.maximum(w["x"] @ w["s_up"], 0) ** 2 @ w["s_down"]
    parts, rows = [], 0
    for chip in range(8):
        out, stats = _share(w, first=2 * chip, held=2)
        parts.append(out - shared)
        rows += int(stats["expert_rows_held"])
        assert int(jnp.sum(stats["expert_tokens"])) == 64 * 3
        np.testing.assert_array_equal(
            stats["expert_tokens"],
            np.bincount(np.asarray(chosen).ravel(), minlength=16))
    assert rows == 64 * 3           # every assignment on exactly one chip
    _close(sum(parts) + shared, whole)


_ROUTINGS = ("one_held", "none_held", "all_held", "seeded",
             "just_over_a_pass", "split", "eight_passes")


@functools.lru_cache(maxsize=None)
def _routing_case(routing, first=4, held=4, k=3):
    """(the layer's arguments, the pushed bias, the weights of the scalar
    that is differentiated, the reference's (output, chosen experts) and
    its gradients), once for a routing: neither the kernels' form nor the
    way a pass's rows are added back is the reference's business."""
    T, E = (1200, 32) if routing == "eight_passes" else (480, 16)
    w = _layer_weights(seed=1, T=T, E=E)
    mine = (np.arange(E) >= first) & (np.arange(E) < first + held)
    absent = [e for e in range(E) if not mine[e]]
    push = {"one_held": [first + 1] + absent[:2], "none_held": absent[:3],
            "all_held": [first, first + 1, first + 3], "seeded": [],
            "just_over_a_pass": [first + 1, absent[0]], "split": [],
            "eight_passes": [first, first + 1, first + 3]}[routing]
    bias = w["bias"].at[jnp.array(push, jnp.int32)].add(10.0)
    if routing == "split":
        # one channel that the held experts' scores rise with and the
        # others' fall with, set high in the first half of the tokens and
        # low in the second
        w["x"] = w["x"].at[:, 0].set(
            jnp.where(jnp.arange(T) < T // 2, 2.5, -2.5))
        w["router"] = w["router"].at[0].set(jnp.where(mine, 2.0, -2.0))
    given = (w["x"], w["router"], w["up"][first:first + held],
             w["down"][first:first + held], w["s_up"], w["s_down"])
    weights = jax.random.normal(jax.random.PRNGKey(9), w["x"].shape)

    def plain(x, router, up, down, s_up, s_down):
        return reference._plain_experts(x, router, bias, up, down, s_up,
                                        s_down, k=k, first=first, scale=2.5)

    with jax.default_matmul_precision("highest"):
        (_, (want, chosen, _)), want_grads = _every(plain, weights)(*given)
    return given, bias, weights, (want, chosen), want_grads


def _every(fn, weights):
    """fn's result, and the gradient by every argument of its first
    output's weighted sum, as one program."""
    def scalar(*given):
        made = fn(*given)
        return jnp.sum(made[0] * weights), made
    return jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(6)),
                                      has_aux=True))


@pytest.mark.parametrize("routing", _ROUTINGS)
def test_no_assignment_is_dropped_at_any_routing(form, back, routing):
    """All tokens to one held expert (and two absent), to none, every
    assignment held, a seeded spread, all to one held expert with a few
    to the others besides, half the tokens with all k of theirs held and
    the other half with none, and every assignment held where 4 of 32
    experts are (eight passes): output and every gradient equal the
    reference's under the same bias, by either way of adding a pass's
    rows back, in as many passes of the buffers' rows as the held rows
    take (480 tokens x 3 over 4 of 16 experts: buffers of 512 rows, not
    1,440)."""
    first, held, k = 4, 4, 3
    given, bias, weights, (want, chosen), want_grads = _routing_case(routing)
    T, E = given[0].shape[0], given[1].shape[1]
    assert (T, E) == ((1200, 32) if routing == "eight_passes" else (480, 16))
    plan = held_rows_plan(T, k, held, E)
    R = plan.rows
    assert R == 512 < T * k and plan.gathered == (back == "gathered")

    def program(x, router, up, down, s_up, s_down):
        return held_moe_layer(x, router, bias, up, down, s_up, s_down,
                              experts_per_token=k, first=first,
                              routed_scale=2.5)

    (_, (out, stats)), grads = _every(program, weights)(*given)
    _close(out, want)
    for got, wanted in zip(grads, want_grads):
        _close(got, wanted)
    of_mine = jnp.sum((chosen >= first) & (chosen < first + held), axis=-1)
    in_share = int(jnp.sum(of_mine))
    assert int(stats["expert_rows_held"]) == in_share
    rows, passes = {"one_held": (T, 1), "none_held": (0, 0),
                    "all_held": (T * k, -(-T * k // R)), "seeded": (None, 1),
                    "just_over_a_pass": (None, 2), "split": (T // 2 * k, 2),
                    "eight_passes": (T * k, 8)}[routing]
    if rows is not None:
        assert in_share == rows
    if routing == "just_over_a_pass":
        assert R < in_share < R + R // 4
    if routing == "split":
        np.testing.assert_array_equal(
            of_mine, np.where(np.arange(T) < T // 2, k, 0))
    assert int(stats["expert_passes"]) == passes == -(-in_share // R)


@pytest.mark.parametrize("tokens,k,held,experts,rows,balanced,gathered", [
    # the cell's: 27 tiles, not 192, and 7.1 rows gathered for one scattered
    (16384, 6, 16, 128, 13824, 12288, False),
    (32768, 4, 16, 32, 73728, 65536, True),     # LFM2's cell: 1.78 for one
    (16384, 6, 128, 128, 98304, 98304, True),   # every expert held: T x k
    (2048, 6, 16, 128, 2048, 1536, False),      # 1,728 rounded up to the tile
    (480, 3, 4, 16, 512, 360, False),            # 2.8 for one
    (64, 3, 4, 16, 192, 48, True),              # under a tile: T x k
    (1, 6, 16, 128, 6, 1, True), (3, 6, 16, 128, 18, 3, True)])   # a decode
def test_the_buffers_rows_come_from_the_shapes(tokens, k, held, experts,
                                               rows, balanced, gathered):
    plan = held_rows_plan(tokens, k, held, experts)
    assert plan == (rows, balanced, 512, gathered)
    assert rows <= tokens * k
    assert rows == tokens * k or (
        rows % plan.tile == 0 and 8 * rows >= 9 * balanced)
    # the form follows from the shapes alone: T x k rows gathered against
    # `rows` scattered
    assert gathered == (tokens * k <= moe._GATHERED_BACK_UP_TO * rows)


def test_the_bias_picks_and_never_weighs():
    """Top-k of s + b, weights from s alone renormalised and scaled; a
    bias added to all experts alike changes nothing; none of it reaches a
    gradient."""
    w = _layer_weights(seed=2)
    out, _ = _share(w, 0, 16)
    shifted, _ = _share(w, 0, 16, bias=w["bias"] + 3.0)
    _close(shifted, out, 1e-6)
    s = router_scores(w["x"], w["router"])
    _, chosen = jax.lax.top_k(s + w["bias"], 3)
    picked = jnp.take_along_axis(s, chosen, -1)
    wts = 2.5 * picked / jnp.sum(picked, -1, keepdims=True)
    dense = jnp.einsum("tef,efd->ted", jnp.maximum(jnp.einsum(
        "td,edf->tef", w["x"], w["up"]), 0) ** 2, w["down"])
    routed = jnp.einsum("tk,tkd->td", wts, jnp.take_along_axis(
        dense, chosen[..., None], 1))
    shared = jnp.maximum(w["x"] @ w["s_up"], 0) ** 2 @ w["s_down"]
    _close(out, routed + shared)
    grad = jax.grad(lambda b: jnp.sum(_share(w, 0, 16, bias=b)[0]))(w["bias"])
    assert not grad.any()


def test_balance_bias_evens_a_skewed_router_on_a_fresh_batch():
    """Scores whose experts differ in popularity by far: top-6 of the
    scores alone load the fullest expert many times the mean; under the
    fitted bias a FRESH batch from the same distribution stays under 1.1."""
    E, k, d = 32, 6, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    router = jax.random.normal(ks[0], (d, E)) * d ** -0.5
    common = 2.0 * jax.random.normal(ks[1], (d,))     # what every token has

    def scores(key, n):
        return router_scores(common + jax.random.normal(key, (n, d)), router)

    def load(s, bias):
        _, chosen = jax.lax.top_k(s + bias, k)
        counts = jnp.bincount(chosen.ravel(), length=E)
        return float(jnp.max(counts) / jnp.mean(counts))

    fit, fresh = scores(ks[2], 16384), scores(ks[3], 8192)
    assert load(fresh, 0.0) > 3.0
    bias = balance_bias(fit, k)
    assert load(fit, bias) < 1.03
    assert load(fresh, bias) < 1.1


def test_a_steps_rounds_follow_a_router_that_moved_all_at_once():
    """What AdamW's first step does to a router (PERF.md section 6, PR
    45): every expert's logit moves by up to 0.65, for all tokens alike.
    The bias the old scores were balanced by loads the fullest expert many
    times the mean; 48 rounds from it on the new scores and the held
    sixteenths are within 1% of even, the biases' mean at zero."""
    E, k, n = 128, 6, 4096
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    popularity = jax.random.normal(ks[0], (E,))
    own = 0.3 * jax.random.normal(ks[1], (n, E))      # a token's own part
    moved = 0.65 * jax.random.uniform(ks[2], (E,), minval=-1.0)
    before = jax.nn.sigmoid(popularity + own)
    after = jax.nn.sigmoid(popularity + moved + own)

    def counts(s, bias):
        return np.bincount(np.asarray(jax.lax.top_k(s + bias, k)[1]).ravel(),
                           minlength=E)

    old = balance_bias(before, k)
    assert counts(before, old).max() < 1.05 * n * k / E
    assert counts(after, old).max() > 3 * n * k / E
    new = balance_bias(after, k, 48, old)
    held = counts(after, new).reshape(8, 16).sum(-1) / (n * k / 8)
    assert np.all(np.abs(held - 1) < 0.01), held
    assert abs(float(jnp.mean(new))) < 1e-6


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_published_model_has_31_58_billion_parameters():
    """From the config alone (shapes, nothing made): 23 x 38.74 M + 6 x
    23.40 M + 23 x 1,297.5 M + 704.6 M."""
    import math

    cfg = dataclasses.replace(NemotronHConfig.nemotron_3_nano_30b_a3b(),
                              balance_tokens=0)
    assert (cfg.pattern.count("M"), cfg.pattern.count("E"),
            cfg.pattern.count("*"), cfg.n_layers) == (23, 23, 6, 52)
    shapes = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(math.prod(leaf.shape)      # noqa: E731
                             for leaf in jax.tree.leaves(tree))
    by_kind = {letter: count(shapes["layers"][cfg.pattern.index(letter)])
               for letter in "M*E"}
    assert by_kind == {"M": 38_744_896, "*": 23_399_040, "E": 1_297_468_160}
    assert count(shapes) == 31_577_940_288
    assert round(count(shapes) / 1e9, 2) == 31.58


def test_config_presets_and_parameter_tree(tiny):
    cfg, params, _ = tiny
    dec = cfg.decoder()
    assert dec.kinds == (decoder.MAMBA2_ONLY, decoder.EXPERTS,
                         decoder.MAMBA2_ONLY, decoder.ATTENTION_ONLY,
                         decoder.EXPERTS)
    assert dec.rope_base is None
    mamba, experts, _, attention, _ = params["layers"]
    assert "ln1" in mamba and "ln2" not in mamba and "w_gate" not in mamba
    assert "ln2" in experts and "ln1" not in experts
    assert experts["router"].shape == (64, 8)            # all 8 outputs
    assert experts["expert_up"].shape == (4, 64, 48)     # 4 held
    assert experts["router"].dtype == experts["router_bias"].dtype \
        == jnp.float32
    assert {name: attention[name].shape for name in ("wq", "wkv", "wo")} == {
        "wq": (64, 96), "wkv": (64, 96), "wo": (96, 64)}
    axes = nemotron_h_param_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes,
                     is_leaf=lambda a: isinstance(a, tuple)))


def test_the_initialiser_leaves_the_routers_balanced(tiny):
    """The biases are not zero and nothing else differs; under them a
    FRESH batch from the fit's distribution loads the experts evenly (the
    fullest under 1.1 of the mean, the held experts' rows within 3% of
    their even share a layer) where zero biases do not. Routed by the
    biases as they are: no round of the rule on the batch itself."""
    cfg = dataclasses.replace(tiny[0], balance_tokens=4096, bias_rounds=0)
    params = nemotron_h_init(jax.random.PRNGKey(0), cfg)
    zero = nemotron_h_init(jax.random.PRNGKey(0),
                           dataclasses.replace(cfg, balance_tokens=0))
    tok = jax.random.randint(jax.random.PRNGKey(5), (48, 64), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    for lay, lay0 in zip(params["layers"], zero["layers"]):
        for name in lay:
            same = bool(jnp.array_equal(lay[name], lay0[name]))
            assert same == (name != "router_bias"), name
    balanced = nemotron_h_loss_and_counters(params, batch, cfg)[1]
    unbalanced = nemotron_h_loss_and_counters(zero, batch, cfg)[1]
    assert float(balanced["expert_load_max_over_mean"]) < 1.1
    assert float(unbalanced["expert_load_max_over_mean"]) > 1.3
    even = tok.size * cfg.experts_per_token * cfg.held[1] / cfg.n_experts
    assert np.all(np.abs(np.asarray(balanced["expert_rows_held"]) / even - 1)
                  < 0.03)
    assert balanced["expert_tokens"].shape == (2, 8)


def test_logits_loss_and_every_gradient_equal_the_reference(
        tiny, reference_of_tiny, form):
    cfg, params, batch = tiny
    _close(nemotron_h_forward(params, batch[0], cfg),
           reference.reference_logits(params, batch[0], cfg))
    loss, grads = jax.value_and_grad(nemotron_h_loss)(params, batch, cfg)
    want, want_grads = reference_of_tiny
    assert abs(float(loss) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), wanted in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(got).all(), name
        scale = max(1e-3, float(jnp.max(jnp.abs(wanted))))
        assert float(jnp.max(jnp.abs(got - wanted))) <= 2e-3 * scale, name
    for lay in grads["layers"]:
        if "router_bias" in lay:
            assert not lay["router_bias"].any()
            assert lay["router"].any() and lay["expert_up"].any()


def test_the_programs_routing_is_the_references_own(tiny):
    cfg, params, batch = tiny
    counts = nemotron_h_loss_and_counters(params, batch, cfg)[1][
        "expert_tokens"]
    chosen = reference.reference_routing(params, batch[0], cfg)
    for row, ch in zip(counts, chosen):
        np.testing.assert_array_equal(
            row, np.bincount(np.asarray(ch).ravel(), minlength=cfg.n_experts))


def test_the_all_bfloat16_reference_is_another_number(tiny):
    cfg, params, batch = tiny
    exact = float(reference.reference_loss(params, *batch, cfg))
    low = float(reference.reference_loss(params, *batch, cfg, jnp.bfloat16))
    assert 1e-4 < abs(low - exact) < 0.5


@pytest.mark.parametrize("form,fault", [
    ("jax", None), ("interpreted", None),
    *(("interpreted", fault) for fault in reference.STRUCTURAL_FAULTS)])
def test_the_cell_holds_the_new_layers_to_a_limit_of_their_own(
        monkeypatch, form, fault):
    """kernel_errors at the CPU's size: the program within 1e-3 of the
    plain forms in every value (both forms), every planted fault far
    outside KERNEL_LIMIT in some value (the kernels interpreted), and
    `planted` puts the real ones back."""
    import contextlib

    if form == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)

    cfg = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32,
                              mamba_n_groups=2, n_experts=16,
                              experts_held=(4, 4))
    before = (decoder.ssm_scan, decoder.gated_rms_norm,
              decoder.held_moe_layer)
    with reference.planted(fault) if fault else contextlib.nullcontext():
        errors = reference.kernel_errors(cfg, seed=3, long=64)
    assert (decoder.ssm_scan, decoder.gated_rms_norm,
            decoder.held_moe_layer) == before
    assert len(errors) == 22 and np.isfinite(list(errors.values())).all()
    if fault is None:
        assert max(errors.values()) < 1e-3, errors
    else:
        assert max(errors.values()) > 5 * reference.KERNEL_LIMIT, errors


@pytest.mark.parametrize("fault", list(reference.STRUCTURAL_FAULTS))
def test_a_planted_fault_moves_the_models_loss(tiny, fault):
    cfg, params, batch = tiny
    right = float(nemotron_h_loss(params, batch, cfg))
    with reference.planted(fault):
        wrong = float(jax.jit(
            lambda p, b: nemotron_h_loss(p, b, cfg))(params, batch))
    assert abs(wrong - right) > 1e-4, (fault, right, wrong)


def test_remat_on_equals_remat_off(tiny, monkeypatch):
    """With the kernels interpreted: theirs are the names a block keeps."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg, params, batch = tiny
    grads = [jax.grad(nemotron_h_loss)(
        params, batch, dataclasses.replace(cfg, remat=remat))
        for remat in (True, False)]
    for on, off in zip(*map(jax.tree.leaves, grads)):
        _close(on, off, 1e-5)


def test_prefill_then_decode_equals_the_full_forward(tiny, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg, params, (tok, _) = tiny
    full = nemotron_h_forward(params, tok, cfg)
    cache = init_cache(cfg, tok.shape[0], 32)
    assert [sorted(c) for c in cache] == [
        ["conv", "ssm"], [], ["conv", "ssm"], ["k", "v"], []]
    logits, cache = cached_forward(params, tok[:, :24], cache, 0, cfg)
    _close(logits, full[:, :24])
    for t in range(24, 28):
        logits, cache = cached_forward(params, tok[:, t:t + 1], cache, t, cfg)
        _close(logits[:, 0], full[:, t])


def test_the_bias_is_state_the_optimizer_does_not_own(tiny):
    """No gradient, no moment, no weight decay: the parameters and the
    optimizer's state have no `router_bias`; state["held"] moves to what
    `bias_rounds` rounds of its own rule give on the step's own scores, a
    layer at a time, whatever the learning rate and the decay, and the
    step routed by that."""
    import optax

    cfg, params, batch = tiny
    init_state, step = make_nemotron_h_train_step(
        cfg, optimizer=optax.adamw(1e-2, weight_decay=0.5), donate=False)
    state = init_state(jax.random.PRNGKey(0))
    names = {jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 (state["params"], state["opt_state"]))[0]}
    assert names and not any("router_bias" in name for name in names)
    _, biases = split_bias(params, cfg)
    np.testing.assert_array_equal(state["held"], jnp.stack(biases))
    assert state["held"].shape == (2, cfg.n_experts)
    new, metrics = step(state, batch)
    counts = np.asarray(metrics["expert_tokens"], np.float32)
    # the first expert layer's scores are what the untouched parameters
    # give: its bias is balance_bias of them from the initialiser's, and
    # its counts are the top k under that
    layer = params["layers"][1]
    scores = router_scores(
        rms_norm(decoder._block(
            jnp.take(params["embed"], batch[0], axis=0), params["layers"][0],
            None, None, dec=cfg.decoder(), kind=decoder.MAMBA2_ONLY)[0],
            layer["ln2"], cfg.norm_eps).reshape(-1, cfg.d_model),
        layer["router"])
    want = balance_bias(scores, cfg.experts_per_token, cfg.bias_rounds,
                        layer["router_bias"])
    np.testing.assert_allclose(new["held"][0], want, atol=1e-6)
    _, chosen = jax.lax.top_k(scores + new["held"][0], cfg.experts_per_token)
    np.testing.assert_array_equal(
        counts[0], np.bincount(np.asarray(chosen).ravel(),
                               minlength=cfg.n_experts))
    assert (np.abs(np.asarray(new["held"] - state["held"])) > 0).any()
    np.testing.assert_array_equal(metrics["router_bias"], new["held"])
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(
        float(jnp.max(jnp.abs(new["held"]))))
    assert int(metrics["expert_rows_held"].sum()) == int(
        counts[:, cfg.held[0]:sum(cfg.held)].sum())
    # 64 tokens x 3: the buffers hold them all, one pass a layer
    np.testing.assert_array_equal(metrics["expert_passes"], [1, 1])
    # the counters the loss gave are the loss function's own
    _, counters = nemotron_h_loss_and_counters(params, batch, cfg)
    np.testing.assert_array_equal(metrics["expert_tokens"],
                                  counters["expert_tokens"])


def test_tiny_train_step_learns_and_stays_balanced(tiny):
    cfg, _, batch = tiny
    init_state, step = make_nemotron_h_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5
    assert int(state["step"]) == 8


def test_counts_equal_hand_counts_at_the_published_sizes():
    """The count functions from the configuration's file, against ISSUE
    45's arithmetic: per token forward, Mamba-2 projections 310 MF, the
    attention layer 181 (134 of it the maps at 16,384), shared experts
    160, the balanced routed share 60, the head 88; 2.4 GF a token to
    train."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    S = T = 16384
    d, inner, conv = 2688, 4096, 4096 + 2 * 8 * 128
    scan = 128 * 128 * 8 + 128 * inner + 4 * inner * 128
    mamba = 2 * d * (inner + conv + 64) + 2 * 4 * conv + scan + 2 * inner * d
    attention = 2 * d * 4096 * 2 + 2 * d * 512 + 2 * 2 * S * 4096 / 2
    experts = 2 * d * 128 + 0.75 * 4 * d * 1856 + 4 * d * 3712
    forward = 4 * mamba + attention + 4 * experts + 2 * d * 16384
    assert reference.forward_flops_per_token(config, S) == forward
    assert reference.train_flops_per_token(config, S) == 3 * forward
    assert 2.3e9 < 3 * forward < 2.5e9
    assert round(4 * 2 * d * (inner + conv + 64 + inner) / 1e6) == 310
    assert round(attention / 1e6) == 181
    assert reference.attention_kernel_flops(config, 1, S) == \
        6 * 2 * S * S * 4096 / 2
    assert reference.attention_kernel_bytes(config, 1, S) == \
        6 * S * 4096 * 2 + 6 * S * 256 * 2
    assert reference.ssm_scan_flops(config, 1, S) == 4 * 3 * S * scan
    assert reference.ssm_scan_bytes(config, 1, S) == 4 * (
        S * (5 * inner * 2 + 3 * 64 * 4 + 3 * 2048 * 2)
        + 2 * 128 * inner * 128 * 4)
    rows = 0.75 * T                             # 12,288 a layer
    assert reference.expert_matmul_flops(config, T) == \
        4 * 6 * 2 * rows * d * 1856
    assert reference.expert_matmul_bytes(config, T) == \
        4 * 6 * 2 * (rows * (d + 1856) + 16 * d * 1856)
    cfg = reference.build(config)
    assert reference.train_flops_per_token(cfg, S) == 3 * forward
    assert (cfg.held, cfg.n_experts, cfg.vocab_size, cfg.pattern) == (
        (0, 16), 128, 16384, "MEMEM*EME")
