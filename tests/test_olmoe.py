"""OLMoE through the program: models/moe.py against the plain float32
reference (chipbench/families/olmoe.py, the one copy), the dropless
top-k layer (parallel/moe.py), the grouped-matmul kernels
(ops/grouped_matmul.py) and the names and counters the step carries.
CPU, tiny sizes, seeded weights."""

import collections
import dataclasses
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from chipbench.families import olmoe
from ray_tpu import train
from ray_tpu.models import decoder
from ray_tpu.models import (
    MoEConfig,
    make_moe_train_step,
    moe_forward,
    moe_init,
    moe_loss,
    moe_loss_and_counters,
)
from ray_tpu.parallel.moe import dropless_moe_layer
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.util import profiling

# `ray_tpu.ops.grouped_matmul` the attribute is the function; the module:
gm = importlib.import_module("ray_tpu.ops.grouped_matmul")

F32 = dataclasses.replace(MoEConfig.tiny(), dtype=jnp.float32, n_experts=8,
                          experts_per_token=3)


def _tokens(cfg, batch=2, seq=32, seed=2):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              cfg.vocab_size)
    return toks, jnp.roll(toks, -1, 1)


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
# float32 program against the float32 reference: the same mathematics in
# another order (sorted rows against every expert for every token), so
# they differ by float32 rounding only, 1e-6 of scale; 1e-4 leaves room
# and would not pass a renormalised weight, a missing q/k norm or a
# dropped assignment (each moves logits by 1e-2 or more). bf16 weights
# against the float32 reference on the same weights: bf16 has 8 bits, two
# layers of matmuls give about 2e-2 of the logits' scale.
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_forward_logits_match_the_reference(dtype, tol):
    cfg = dataclasses.replace(F32, dtype=dtype)
    params = moe_init(jax.random.PRNGKey(1), cfg)
    toks, _ = _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = moe_forward(params, toks, cfg)
        want = olmoe.reference_logits(params, toks, cfg)
    assert got.shape == (2, 32, cfg.vocab_size) and got.dtype == jnp.float32
    assert _rel(got, want) < tol
    assert float(aux) > 0


@pytest.mark.parametrize("variant", ["olmoe", "tied_no_qk_norm_normed"])
def test_loss_and_every_gradient_match_the_reference(variant):
    cfg = F32 if variant == "olmoe" else dataclasses.replace(
        F32, tie_embeddings=True, qk_norm=False, norm_topk_prob=True)
    params = moe_init(jax.random.PRNGKey(3), cfg)
    assert ("head" in params) == (variant == "olmoe")
    batch = _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(moe_loss)(params, batch, cfg)
        want, g_want = jax.value_and_grad(
            lambda p: olmoe.reference_loss(p, *batch, cfg))(params)
    assert abs(float(got) - float(want)) < 1e-5
    # float32 rounding in another order: 1e-6 of each leaf's scale seen.
    errs = jax.tree.map(_rel, g_got, g_want)
    assert max(jax.tree.leaves(errs)) < 1e-4, errs
    assert float(jnp.abs(g_got["layers"][0]["router"]).max()) > 0


def test_balanced_router_gives_balance_loss_k_and_z_log_e_squared():
    """A router of zeros: p = 1/E everywhere, sum_e f_e = k, so L_balance
    = E * k / E = k whichever k experts the ties pick; L_z = (ln E)^2."""
    cfg = F32
    params = moe_init(jax.random.PRNGKey(0), cfg)
    for lay in params["layers"]:
        lay["router"] = jnp.zeros_like(lay["router"])
    _, c = moe_loss_and_counters(params, _tokens(cfg), cfg)
    assert float(c["balance_loss"]) == pytest.approx(cfg.experts_per_token)
    assert float(c["router_z"]) == pytest.approx(
        np.log(cfg.n_experts) ** 2, rel=1e-5)
    assert int(c["expert_tokens"].sum()) == 2 * 32 * 3 * cfg.n_layers


def test_olmoe_1b_7b_preset_is_the_published_model():
    cfg = MoEConfig.olmoe_1b_7b()
    shapes = jax.eval_shape(lambda: moe_init(jax.random.PRNGKey(0), cfg))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    lay = shapes["layers"][0]
    experts = sum(lay[k].size for k in ("expert_gate", "expert_up",
                                        "expert_down"))
    assert total == pytest.approx(6.92e9, rel=2e-3)
    assert experts == 402_653_184
    active = total - cfg.n_layers * experts * (1 - 8 / 64)
    assert active == pytest.approx(1.28e9, rel=2e-2)
    assert lay["expert_gate"].dtype == jnp.bfloat16
    assert lay["router"].dtype == jnp.float32
    assert shapes["head"].shape == (2048, 50304)


def test_counts_equal_hand_counts_from_dict_and_config_object():
    config = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_hidden_layers": 2, "num_experts": 64,
              "num_experts_per_tok": 8, "intermediate_size": 1024,
              "vocab_size": 50304}
    cfg = dataclasses.replace(MoEConfig.olmoe_1b_7b(), n_layers=2)
    layer = (2 * 2048 * 6144 + 2 * 2048 * 2048 + 2 * 2048 * 64
             + 8 * 3 * 2 * 2048 * 1024 + 2 * 2 * 4096 * 2048 / 2)
    want = 3 * (2 * layer + 2 * 2048 * 50304)
    assert want == pytest.approx(1.526e9, rel=1e-3)
    for c in (config, cfg):
        assert olmoe.train_flops_per_token(c, 4096) == want
        assert olmoe.expert_matmul_flops(c, 16384) == \
            2 * 9 * 2 * 131072 * 2048 * 1024
        assert olmoe.expert_matmul_bytes(c, 16384) == \
            2 * 9 * 2 * (131072 * 3072 + 64 * 2048 * 1024)
        assert olmoe.attention_kernel_flops(c, 4, 4096) == \
            2 * 6 * 2 * 4 * 16 * 4096 * 4096 * 128 / 2
        assert olmoe.attention_kernel_bytes(c, 4, 4096) == \
            2 * 12 * 4 * 16 * 4096 * 128 * 2


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------
def _layer_inputs(t=64, d=32, f=48, e=64, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(k[0], (t, d), dtype),
        router=jax.random.normal(k[1], (d, e)) * d ** -0.5,
        gate=(jax.random.normal(k[2], (e, d, f)) * d ** -0.5).astype(dtype),
        up=(jax.random.normal(k[3], (e, d, f)) * d ** -0.5).astype(dtype),
        down=(jax.random.normal(k[4], (e, f, d)) * f ** -0.5).astype(dtype))


def _dense_reference(a, k, norm=False):
    """The reference's every-expert-for-every-token layer on the same
    tensors."""
    lay = {"router": a["router"], "expert_gate": a["gate"],
           "expert_up": a["up"], "expert_down": a["down"]}
    cfg = MoEConfig(n_experts=a["router"].shape[1], experts_per_token=k,
                    norm_topk_prob=norm)
    return olmoe._experts(a["x"].astype(jnp.float32), jax.tree.map(
        lambda w: w.astype(jnp.float32), lay), cfg)


@pytest.mark.parametrize("kernels", ["interpreted", "ragged_dot"])
def test_no_token_is_dropped_under_forced_imbalance(kernels, monkeypatch):
    """A constant input feature and a router row that favours 8 of 64
    experts send every token to the same 8: a capacity of 1.25 * T * 8 / 64
    would drop 84% of the assignments; here all T * 8 arrive and 56 groups
    are empty."""
    if kernels == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(gm, "_TILES", (128, 128, 128))
    a = _layer_inputs(t=64)
    favoured = jnp.arange(8) * 7 + 3
    a["x"] = a["x"].at[:, 0].set(10.0)
    a["router"] = a["router"].at[0, :].set(0.0).at[0, favoured].set(5.0)
    with jax.default_matmul_precision("highest"):
        out, stats = dropless_moe_layer(
            a["x"], a["router"], a["gate"], a["up"], a["down"],
            experts_per_token=8)
        want, _, chosen = _dense_reference(a, 8)
    counts = np.asarray(stats["expert_tokens"])
    assert set(np.asarray(chosen).ravel()) == set(np.asarray(favoured))
    assert counts.sum() == 64 * 8
    assert (counts == 0).sum() == 56
    assert (counts[np.asarray(favoured)] == 64).all()
    assert _rel(out, want) < 1e-5


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_false_leaves_the_weights_unnormalised(norm):
    a = _layer_inputs(t=32, e=8)
    with jax.default_matmul_precision("highest"):
        out, _ = dropless_moe_layer(
            a["x"], a["router"], a["gate"], a["up"], a["down"],
            experts_per_token=2, norm_topk_prob=norm)
        want, logits, _ = _dense_reference(a, 2, norm=norm)
        normed, _ = dropless_moe_layer(
            a["x"], a["router"], a["gate"], a["up"], a["down"],
            experts_per_token=2, norm_topk_prob=True)
    assert _rel(out, want) < 1e-5
    # The two chosen probabilities sum to well under 1 with 8 experts;
    # unnormalised, the output is the normalised one times that sum.
    top2 = jnp.sum(jax.lax.top_k(jax.nn.softmax(logits, -1), 2)[0], -1)
    assert float(top2.max()) < 0.9
    scale = 1.0 if norm else top2[:, None]
    assert _rel(out, normed * scale) < 1e-5


def _written_out_layer(x, router, gate, up, down, k, norm):
    """The layer's equations in jnp alone, for plain autodiff: no
    custom_vjp, no kernel, no inverse permutation; the weights multiply
    the experts' rows after the down matmul, as the docstring's formula
    has them. The program's own rounding points: rows in x's dtype,
    products accumulated in float32."""
    t, d = x.shape
    f32 = jnp.float32

    def matmul(rows, w):
        return jax.lax.ragged_dot(rows, w, counts,
                                  preferred_element_type=f32).astype(x.dtype)

    logits = jnp.dot(x.astype(f32), router.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if norm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    perm = jnp.argsort(experts.reshape(-1), stable=True)
    counts = jnp.bincount(experts.reshape(-1),
                          length=router.shape[1]).astype(jnp.int32)
    xs = x[perm // k]
    hidden = (jax.nn.silu(matmul(xs, gate).astype(f32))
              * matmul(xs, up).astype(f32)).astype(x.dtype)
    ys = matmul(hidden, down)
    per_token = jnp.zeros_like(ys).at[perm].set(ys).reshape(t, k, d)
    out = jnp.sum(per_token.astype(f32) * weights[:, :, None], axis=1)
    return out.astype(x.dtype), counts


# The routed experts have one gradient rule written by hand
# (parallel/moe.py `_experts`), and the driver's `correct` cannot see a
# wrong gradient (PERF.md §7, item 13): so the rule against plain autodiff
# of the layer written out, on a router that starves one expert and
# favours two. float32: the same sums in another order (and the weights
# multiplied in before the down matmul, not after), 5e-7 of a leaf's scale
# seen. bf16: the rule rounds w * silu(gate) * up where the written-out
# layer rounds silu(gate) * up, 2^-8 a rounding; they differ by up to
# 7.6e-3 of a leaf's scale, each within 7e-3 of the same layer in float32.
@pytest.mark.parametrize("norm", [False, True])
@pytest.mark.parametrize("kernels", ["interpreted", "ragged_dot"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_experts_rule_equals_autodiff_of_the_written_out_layer(
        dtype, tol, kernels, norm, monkeypatch):
    if kernels == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(gm, "_TILES", (128, 128, 128))
    t, e, k = 96, 8, 3
    a = _layer_inputs(t=t, e=e, dtype=dtype, seed=4)
    a["x"] = a["x"].at[:, 0].set(4.0)
    a["router"] = a["router"].at[0, :].set(0.0).at[0, 5].set(-5.0) \
        .at[0, jnp.array([1, 2])].set(0.5)
    ct = jax.random.normal(jax.random.PRNGKey(9), a["x"].shape, dtype)
    args = (a["x"], a["router"], a["gate"], a["up"], a["down"])

    def program(*xs):
        out, stats = dropless_moe_layer(*xs, experts_per_token=k,
                                        norm_topk_prob=norm)
        return out, stats["expert_tokens"]

    with jax.default_matmul_precision("highest"):
        (out, counts), vjp = jax.vjp(program, *args)
        (want, want_counts), want_vjp = jax.vjp(
            lambda *xs: _written_out_layer(*xs, k, norm), *args)
        zero = np.zeros(counts.shape, jax.dtypes.float0)
        grads, want_grads = vjp((ct, zero)), want_vjp((ct, zero))
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts, np.asarray(want_counts))
    assert counts[5] == 0 and counts.sum() == t * k
    assert counts.max() > 1.5 * t * k / e
    assert out.dtype == dtype and _rel(out, want) < tol
    names = ("x", "router (through w)", "expert_gate", "expert_up",
             "expert_down")
    errs = {n: _rel(g, w) for n, g, w in zip(names, grads, want_grads,
                                             strict=True)}
    assert max(errs.values()) < tol, errs
    assert all(g.dtype == x.dtype and float(jnp.abs(g).max()) > 0
               for g, x in zip(grads, args))
    assert float(jnp.abs(grads[2][5]).max()) == 0.0    # the starved expert


def _all_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_avals(sub)


def test_layer_holds_no_dispatch_tensor_and_no_float32_expert_copy():
    t, d, f, e, k = 256, 32, 48, 16, 4
    a = _layer_inputs(t=t, d=d, f=f, e=e, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda *xs: dropless_moe_layer(*xs, experts_per_token=k)[0])(
        a["x"], a["router"], a["gate"], a["up"], a["down"])
    avals = [v for v in _all_avals(jaxpr.jaxpr) if hasattr(v, "shape")]
    capacity = int(1.25 * t * k / e)
    assert max(int(np.prod(v.shape)) for v in avals) < t * e * capacity
    assert any(v.shape == (t * k, d) and v.dtype == jnp.bfloat16
               for v in avals)                   # T*k rows, always
    for v in avals:
        if v.shape in ((e, d, f), (e, f, d)):
            assert v.dtype == jnp.bfloat16, v


# ---------------------------------------------------------------------------
# the grouped matmul
# ---------------------------------------------------------------------------
SIZES = (0, 100, 0, 300, 112, 0)      # uneven, empty groups, 512 rows


def _loop(lhs, rhs):
    """Per group, in a loop: rows of group g times rhs[g]."""
    out, start = [], 0
    for g, n in enumerate(SIZES):
        out.append(lhs[start:start + n] @ rhs[g])
        start += n
    return jnp.concatenate(out)


@pytest.mark.parametrize("kernels", ["interpreted", "ragged_dot"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_grouped_matmul_and_both_gradients(kernels, dtype, tol, monkeypatch):
    """float32: accumulation order only, 1e-6 seen. bf16 operands,
    float32 accumulation, one bf16 rounding of the output: 2^-8 = 4e-3 of
    a value, against a float32 loop on the same bf16 inputs."""
    if kernels == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        # 128-row tiles: groups start inside tiles and span several.
        monkeypatch.setattr(gm, "_TILES", (128, 128, 128))
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(k[0], (512, 256), dtype)
    rhs = jax.random.normal(k[1], (len(SIZES), 256, 384), dtype)
    ct = jax.random.normal(k[2], (512, 384), dtype)
    sizes = jnp.asarray(SIZES, jnp.int32)
    f32 = [x.astype(jnp.float32) for x in (lhs, rhs, ct)]
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda a, b: gm.grouped_matmul(a, b, sizes), lhs, rhs)
        dlhs, drhs = vjp(ct)
        want, want_vjp = jax.vjp(_loop, f32[0], f32[1])
        want_dlhs, want_drhs = want_vjp(f32[2])
    assert out.dtype == dtype and drhs.dtype == dtype
    assert _rel(out, want) < tol
    assert _rel(dlhs, want_dlhs) < tol
    assert _rel(drhs, want_drhs) < tol
    assert float(jnp.abs(drhs[0]).max()) == 0.0       # an empty group


# ---------------------------------------------------------------------------
# what a rematerialised block keeps (models/decoder.py KEPT_UNDER_REMAT)
# ---------------------------------------------------------------------------
def _kernel_path(kernels, monkeypatch):
    monkeypatch.setattr(gm, "_TILES", (128, 128, 128))
    if kernels == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# A kept value is bit for bit the value the second forward would have
# made, so remat changes no number: equality, not a tolerance. That is
# said of the operations as written, so they run one by one, not under
# one jit: compiled whole, where the block is cut moves XLA's fusions, and
# with them which bf16 chains stay in float32 and the order of a fused
# sum (3e-6 of a norm's gradient on the CPU). 128 positions so that the
# interpreted case runs the attention kernels too.
@pytest.mark.parametrize("kernels", ["interpreted", "ragged_dot"])
def test_remat_changes_no_loss_counter_or_gradient(kernels, monkeypatch):
    _kernel_path(kernels, monkeypatch)
    on = MoEConfig.tiny()
    off = dataclasses.replace(on, remat=False)
    assert on.remat and on.decoder().remat is decoder.keep_kernel_outputs
    assert off.decoder().remat is None
    params = moe_init(jax.random.PRNGKey(0), on)
    batch = _tokens(on, batch=1, seq=128)

    def value_and_grads(cfg):
        return jax.value_and_grad(
            lambda p: moe_loss_and_counters(p, batch, cfg),
            has_aux=True)(params)

    (loss_on, counters_on), grads_on = value_and_grads(on)
    (loss_off, counters_off), grads_off = value_and_grads(off)
    assert float(loss_on) == float(loss_off)
    for a, b in zip(jax.tree.leaves((counters_on, grads_on)),
                    jax.tree.leaves((counters_off, grads_off)), strict=True):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _count_kernels_and_row_gathers(jaxpr, rows_shape, counts):
    """Walk a jaxpr and what it holds: pallas_calls by the DEVICE_SCOPES
    name they stand under, gathers that make a `rows_shape` value, and
    top-k choices a rematerialised body makes from a value it made again
    (one that reads a kept value chooses as the forward pass did)."""
    for eqn in jaxpr.eqns:
        if "policy" in eqn.params:              # a jax.checkpoint's body
            body = eqn.params["jaxpr"]
            counts["choices_made_again"] += sum(
                e.primitive.name == "top_k" and e.invars[0] not in body.invars
                for e in body.eqns)
        if eqn.primitive.name == "pallas_call":
            scope = str(eqn.source_info.name_stack).split("/")[-1]
            counts[next(s for s in profiling.DEVICE_SCOPES
                        if s in scope)] += 1
        elif (eqn.primitive.name == "gather"
              and eqn.outvars[0].aval.shape == rows_shape):
            counts["row_gathers"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count_kernels_and_row_gathers(sub, rows_shape, counts)
    return counts


# One block's gradient, traced with the kernels in it (no chip needed to
# trace). A layer is one attention and three grouped matmuls forward, each
# with its two gradients, and four [T*k, d] gathers: the dispatch and the
# combine's rows back in token order, and in the backward pass the same
# two with their roles exchanged (the output's cotangent spread over the
# sorted rows from the T tokens' rows, the dispatch's cotangent summed
# back) -- the routed experts' one gradient rule, parallel/moe.py
# `_experts`. Remat adds nothing to that: no kernel's forward runs twice,
# and the dispatched rows are kept (`moe_xs`), so there are 4 gathers where
# PR 28's blocks made 5 (the dispatch again) and, keeping nothing, PR 27's
# made 6 with 6 grouped_matmul_fwd and 2 flash_attention_fwd. And the
# backward pass sorts by the forward's choice of experts: its top-k reads
# the kept probabilities. With the router made again and the gate and up
# rows kept, every expert's weight gradient was 20-90% off on the chip
# (PERF.md §6, PR 28): a flipped near tie shifts the sorted rows, and no
# test on the CPU, where both passes round alike, sees it.
@pytest.mark.parametrize("remat,row_gathers", [(True, 4), (False, 4)])
def test_no_forward_kernel_runs_twice_in_a_block(remat, row_gathers,
                                                 monkeypatch):
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(MoEConfig.tiny(), n_layers=1, remat=remat)
    params = jax.eval_shape(lambda: moe_init(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    grad = jax.make_jaxpr(jax.grad(
        lambda p, t: moe_loss(p, (t, t), cfg)))(params, tok)
    rows = (2 * 128 * cfg.experts_per_token, cfg.d_model)
    assert _count_kernels_and_row_gathers(
        grad.jaxpr, rows, collections.Counter()) == collections.Counter({
            "flash_attention_fwd": 1, "flash_attention_dq": 1,
            "flash_attention_dkv": 1, "grouped_matmul_fwd": 3,
            "grouped_matmul_dlhs": 3, "grouped_matmul_drhs": 3,
            "row_gathers": row_gathers, "choices_made_again": 0})


# What leaves a rematerialised block for its backward pass, besides its
# arguments: the ten named values and nothing else. Of the [T*k, d] values
# of a layer (dispatched rows, down matmul's output, its rows in token
# order) one is kept, and it is the dispatched rows: the experts' rule
# (parallel/moe.py `_experts`) multiplies the weights in ahead of the down
# matmul, so its backward reads no output of the experts, and the chip
# says gathering `xs` again costs more than keeping it (models/decoder.py
# KEPT_UNDER_REMAT has the numbers).
@pytest.mark.parametrize("dtype,short", [(jnp.bfloat16, "bf16"),
                                         (jnp.float32, "f32")])
def test_a_block_keeps_the_named_values_and_nothing_else(dtype, short,
                                                         monkeypatch, capsys):
    _kernel_path("interpreted", monkeypatch)
    cfg = dataclasses.replace(MoEConfig.tiny(), n_layers=1, dtype=dtype)
    dec = cfg.decoder()
    b, s, d, k = 2, 128, cfg.d_model, cfg.experts_per_token
    layer = moe_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    block = jax.checkpoint(
        functools.partial(decoder._block, dec=dec, kind=dec.kinds[0],
                          mlp=dec.mlp[0]),
        policy=dec.remat)
    print_saved_residuals(lambda x, layer: block(x, layer, None, None)[0],
                          jnp.ones((b, s, d), dtype), layer)
    # a line: `bf16[256,64] from the argument x`, `... named 'n' from f.py`
    lines = capsys.readouterr().out.splitlines()
    kept = sorted(line.split()[0] for line in lines
                  if "from the argument" not in line
                  and "from a constant" not in line)
    heads = f"{short}[{b},{cfg.n_heads},{s},{cfg.head_dim}]"
    # the ten this block makes, and a Mamba-2, a Mamba-1 and a
    # gated-delta-rule layer's two, two and three (it has none of them)
    assert len(decoder.KEPT_UNDER_REMAT) == 17
    assert kept == sorted([
        f"{short}[{b},{s},{3 * d}]",                    # attention_qkv
        heads, heads, heads, heads,     # flash_attention_q, _k, _v, _out
        f"f32[{b},{cfg.n_heads},1,{s}]",                # flash_attention_lse
        f"f32[{b * s},{cfg.n_experts}]",                # moe_probs
        f"{short}[{b * s * k},{d}]",                    # moe_xs
        f"{short}[{b * s * k},{cfg.d_expert}]",         # moe_gate
        f"{short}[{b * s * k},{cfg.d_expert}]",         # moe_up
    ])
    # Remat puts a reduce_precision after a kept value the forward reads
    # too (a plain copy on the chip, after a kernel or a gather), and the
    # description then names that; one that is a residual of a gradient
    # rule and nothing else still reads by name: attention's lse and the
    # routed experts' three.
    for name in ("flash_attention_lse", "moe_xs", "moe_gate", "moe_up"):
        assert any(f"named '{name}'" in line for line in lines), name
    assert not any("named 'flash_attention_out'" in line for line in lines)


# ---------------------------------------------------------------------------
# names and counters
# ---------------------------------------------------------------------------
SCOPES = ("grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs",
          "moe_route", "moe_combine")


def test_lowered_step_carries_the_scopes_and_kernels_and_no_top2_gating(
        monkeypatch):
    from ray_tpu.ops import attention

    assert all(s in profiling.DEVICE_SCOPES for s in SCOPES)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = MoEConfig(vocab_size=512, d_model=128, n_heads=1, n_layers=1,
                    n_experts=4, experts_per_token=2, d_expert=128,
                    max_seq_len=256)
    init_state, step = make_moe_train_step(cfg)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    text = step.trace(state, (tok, tok)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == set(
        olmoe.MOSAIC_KERNELS)
    for scope in SCOPES[:3]:
        assert re.search(r'loc\("[^"]*/%s/pallas_call"' % scope, text), scope
    for scope in SCOPES[3:] + ("loss", "optimizer_update"):
        assert re.search(r'loc\("jit\(train_step\)/[^"]*\b%s\b' % scope,
                         text), scope
    # call frames by function name: the dropless layer's, none of GShard's
    assert '"dropless_moe_layer"' in text
    assert '"top2_gating"' not in text and '"moe_layer"' not in text


def test_step_returns_the_router_counters_beside_the_loss():
    cfg = MoEConfig.tiny()
    init_state, step = make_moe_train_step(cfg, donate=False)
    state = init_state(jax.random.PRNGKey(0))
    state, m = step(state, _tokens(cfg, batch=4, seq=16))
    assert set(m) == {"loss", "expert_tokens", "expert_load_max_over_mean",
                      "router_z", "balance_loss"}
    assert m["expert_tokens"].shape == (cfg.n_experts,)
    assert int(m["expert_tokens"].sum()) == \
        4 * 16 * cfg.experts_per_token * cfg.n_layers
    assert float(m["expert_load_max_over_mean"]) >= 1.0
    assert np.isfinite([float(m["loss"]), float(m["router_z"]),
                        float(m["balance_loss"])]).all()


def test_train_report_passes_the_counters_on(ray_start_shared, tmp_path):
    def loop(config):
        import jax as jax_

        from ray_tpu.models import MoEConfig as Cfg
        from ray_tpu.models import make_moe_train_step as make

        cfg = Cfg.tiny()
        init_state, step = make(cfg, donate=False)
        toks = jax_.random.randint(jax_.random.PRNGKey(1), (2, 16), 0,
                                   cfg.vocab_size)
        _, metrics = step(init_state(jax_.random.PRNGKey(0)), (toks, toks))
        train.report(jax_.device_get(metrics))

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="olmoe_counters",
                             storage_path=str(tmp_path))).fit()
    assert result.error is None, result.error
    m = result.metrics
    assert int(np.sum(m["expert_tokens"])) == 2 * 16 * 2 * 2
    assert m["expert_load_max_over_mean"] >= 1.0 and m["loss"] > 0
