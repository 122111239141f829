"""models.glm4_moe_lite (GLM-4.7-Flash: a multi-token-prediction module
behind a plain-residual stack of latent attention at a value head wider
than its no-rope key, a dense SwiGLU layer and then a held share of SwiGLU
experts behind a sigmoid router with a selection bias, beside a shared
expert, an untied head) against the benchmark's plain float32 reference
(chipbench/families/glm4_moe_lite.py) on seeded weights, and the pieces
this family brought to models/decoder.py and ops/loss.py: the prediction
module behind `decoder_hidden`, a second, masked cross entropy over the one
head, the plan's account of both."""

import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import glm4_moe_lite as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig, _next_targets,
                                          glm4_moe_lite_forward,
                                          glm4_moe_lite_init,
                                          glm4_moe_lite_loss,
                                          glm4_moe_lite_loss_and_counters,
                                          glm4_moe_lite_param_axes,
                                          make_glm4_moe_lite_train_step,
                                          split_bias, with_bias)
from ray_tpu.models.llama import LlamaConfig, llama_loss
from ray_tpu.ops.loss import cross_entropy, working_set_bytes
from ray_tpu.parallel.moe import held_moe_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "chipbench/configs/glm-4.7-flash.json"
# float32 program against float32 reference: the same sums in another
# order (a latent and one product more against per-head keys and values;
# sorted rows and grouped products against every expert on every token).
TOL = 1e-4
# bfloat16 program against the float32 reference on a loss of 7.2: a
# rounding is 2^-8 and a gradient is some ten of them deep. The limits that
# separate the precisions are the cell's, read on the chip.
TOL_BF16_LOSS = 4e-3
TOL_BF16 = 8e-2


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tiny(dtype=jnp.float32, **changes):
    return dataclasses.replace(Glm4MoeLiteConfig.tiny(), dtype=dtype,
                               **changes)


# The whole-model cases' size: a dense and an expert layer and the module,
# few rounds of the bias's rule, the biases from zero (the train-step case
# keeps the balanced start).
CHEAP = dict(n_layers=2, bias_rounds=8, balance_tokens=0)


def _drawn_apart(params, key=7):
    """`params` with every norm's weight off one and the module's three at
    a spread of their own: at ones a swapped or a left-out norm's weight
    would not show."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(key), len(leaves))
    return jax.tree.unflatten(tree, [
        1.0 + 0.2 * jax.random.normal(k, a.shape)
        if a.ndim == 1 and a.shape[0] != 8 else a
        for k, a in zip(keys, leaves)])


@pytest.fixture(scope="module")
def tiny():
    """(config in float32, the dense layer, one expert layer and the module
    of it, its seeded weights with every norm drawn off one, a batch of two
    40-token sequences)."""
    cfg = _tiny(**CHEAP)
    assert cfg.n_experts == 8       # what `_drawn_apart` tells a bias by
    params = _drawn_apart(glm4_moe_lite_init(jax.random.PRNGKey(0), cfg))
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
        lambda p, b: glm4_moe_lite_loss(p, b, cfg), params, batch)
    assert abs(float(got) - float(want)) <= TOL * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(dwant))
    seen = set()
    for path, g in jax.tree_util.tree_leaves_with_path(dgot):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            continue                        # no gradient reaches it
        w = flat_want[path]
        assert float(jnp.max(jnp.abs(w))) > 0, path
        _close(g, w)
        seen.add(name)
    # the module's own weights, its block's and the shared arrays among them
    for name in ("['mtp']['enorm']", "['mtp']['hnorm']", "['mtp']['w_eh']",
                 "['mtp']['norm']", "['mtp']['block']['w_qa']",
                 "['mtp']['block']['expert_gate_up']", "['embed']",
                 "['head']"):
        assert name in seen, name


def test_the_tables_gradient_is_the_sum_of_its_uses_and_the_heads_of_two(
        tiny):
    """E and the head are the main model's own arrays in the module: the
    table's gradient is the main lookup's plus the module's lookup's (and
    nothing tied to the head), the head's the main loss's plus 0.3 times
    the module's loss's. The parts are the reference's, each use given an
    array of its own; and the program with its module's lookup cut off
    from the table (the benchmark's planted fault) gives the main
    lookup's part alone."""
    cfg, params, batch = tiny
    tokens, targets = batch
    p = reference._as(params, None)

    def parts_of(embed_main, embed_module, head_main, head_module):
        h = reference._stack({**p, "embed": embed_main}, tokens, cfg,
                             cfg.bias_rounds)
        x = reference._rms_norm(h, p["lnf"], cfg.norm_eps)
        x_next = reference._module(h, embed_module[targets], p["mtp"], cfg,
                                   cfg.bias_rounds)
        two_on, valid = _next_targets(targets)
        return (reference._mean_cross_entropy(x, head_main, targets)
                + cfg.mtp_loss_weight * reference._mean_cross_entropy(
                    x_next, head_module, two_on, valid))

    embed, head = p["embed"], p["head"]
    parts = jax.jit(jax.grad(parts_of, argnums=(0, 1, 2, 3)))(
        embed, embed, head, head)
    whole = jax.jit(jax.grad(lambda q: glm4_moe_lite_loss(q, batch, cfg)))(
        params)
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    _close(parts[0] + parts[1], whole["embed"])
    _close(parts[2] + parts[3], whole["head"])
    # the module's lookup weighs: its part is no rounding of the main one's
    assert float(jnp.max(jnp.abs(parts[1]))) > 1e-2 * float(
        jnp.max(jnp.abs(parts[0])))
    with reference.planted("table_gradient_dropped"):
        dropped = jax.jit(jax.grad(
            lambda q: glm4_moe_lite_loss(q, batch, cfg)))(params)
    _close(dropped["embed"], parts[0])
    _close(dropped["head"], whole["head"])


def test_the_module_predicts_two_on_and_its_last_position_is_masked(tiny):
    """`loss_mtp` is the mean over positions 0..S-2 of the module's own
    logits' cross entropy against the token two on: the reference's module
    logits, scored here by hand; position S-1 moves nothing."""
    cfg, params, (tok, targets) = tiny
    _, counters = jax.jit(lambda p, b: glm4_moe_lite_loss_and_counters(
        p, b, dataclasses.replace(cfg, bias_rounds=0)))(params,
                                                         (tok, targets))
    logits = reference.reference_module_logits(params, tok, targets, cfg)
    two_on = jnp.roll(targets, -1, 1)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               two_on[..., None], -1)[..., 0]
    assert float(counters["loss_mtp"]) == pytest.approx(
        float(jnp.mean(nll[:, :-1])), rel=TOL)
    main = reference.reference_logits(params, tok, cfg)
    nll_main = -jnp.take_along_axis(jax.nn.log_softmax(main, -1),
                                    targets[..., None], -1)[..., 0]
    assert float(counters["loss_main"]) == pytest.approx(
        float(jnp.mean(nll_main)), rel=TOL)
    two, valid = _next_targets(targets)
    np.testing.assert_array_equal(two, two_on)
    assert valid.shape == targets.shape and not bool(valid[:, -1].any()) \
        and bool(valid[:, :-1].all())
    # the masked row gives no gradient to the rows or the head
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, cfg.d_model))
    dx = jax.grad(lambda x: cross_entropy(x, params["head"], two, valid))(x)
    assert float(jnp.max(jnp.abs(dx[:, -1]))) == 0.0
    assert float(jnp.max(jnp.abs(dx[:, :-1]))) > 0.0


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
        lambda p, b: glm4_moe_lite_loss(p, b, cfg), params, batch)
    assert abs(float(got) - float(want)) <= TOL_BF16_LOSS * float(want)
    for name in ("embed", "head"):
        _close(dgot[name], dwant[name], TOL_BF16)
    _close(dgot["mtp"]["w_eh"], dwant["mtp"]["w_eh"], TOL_BF16)
    for got_layer, want_layer in zip(
            [*dgot["layers"], dgot["mtp"]["block"]],
            [*dwant["layers"], dwant["mtp"]["block"]]):
        for name in ("w_qa", "w_kvb", "wo"):
            _close(got_layer[name], want_layer[name], TOL_BF16)


@pytest.fixture(scope="module")
def full_forwards(tiny):
    """(the program's training forward, the reference's) of `tiny`'s batch,
    once for both cases below."""
    cfg, params, (tok, _) = tiny
    with jax.default_matmul_precision("highest"):
        return (jax.jit(lambda p, t: glm4_moe_lite_forward(p, t, cfg))(
                    params, tok),
                jax.jit(lambda p, t: reference.reference_logits(p, t, cfg))(
                    params, tok))


@pytest.mark.parametrize("positions", ["scalar", "a_row"])
def test_prefill_then_decode_is_the_references_full_forward(
        tiny, full_forwards, positions):
    """Through the latent cache: a prefill of 33 tokens and 7 single steps
    give the logits of the training forward and of the reference's main
    stack, with the start position a scalar or one a row of the batch; the
    module is training's and keeps no state."""
    cfg, params, (tok, _) = tiny
    cache = init_cache(cfg, 2, 48)
    assert [sorted(layer) for layer in cache] == [["k_rope", "latent"]] * 2
    assert cache[0]["latent"].shape == (2, 48, cfg.kv_lora_rank)
    assert cache[0]["k_rope"].shape == (2, 48, cfg.qk_rope_head_dim)

    def at(t):
        return jnp.int32(t) if positions == "scalar" else jnp.array([t, t])

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


def test_the_published_config_is_the_cells_but_the_three_keys_cut():
    """The cell's configuration against the program's own published one:
    the same widths, five layers of 47, 16 of 64 experts held, a quarter of
    the vocabulary, the module kept; a layer's cache state is 512 + 64
    values a token where per-head keys and values would be 20 x (256 + 256)."""
    with open(os.path.join(ROOT, CONFIG)) as f:
        cfg = reference.build(json.load(f), balance_tokens=0)
    full = Glm4MoeLiteConfig.glm_4_7_flash()
    assert dataclasses.replace(
        full, n_layers=5, experts_held=(0, 16), vocab_size=38720,
        balance_tokens=0) == cfg
    assert cfg.n_predict_layers == 1 and cfg.mtp_loss_weight == 0.3
    assert (cfg.qk_head_dim, cfg.v_head_dim, cfg.n_heads) == (256, 256, 20)
    dec = cfg.decoder()
    assert dec.sm_scale == 1 / 16 and dec.rope_inv_freq is None \
        and dec.rope_base == 1e6 and dec.hyper is None
    assert len(dec.kinds) == 5 and len(cfg.decoder(0, True).kinds) == 6
    cache = init_cache(cfg, 2, 256)
    assert len(cache) == 5          # the module holds none
    for layer in cache:
        assert {k: (v.shape, v.dtype) for k, v in layer.items()} == {
            "latent": ((2, 256, 512), jnp.bfloat16),
            "k_rope": ((2, 256, 64), jnp.bfloat16)}


def test_train_step_keeps_the_biases_apart_and_carries_the_counters():
    cfg = _tiny(n_layers=2)
    init_state, step = make_glm4_moe_lite_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    # one expert layer's and the module's block's
    assert state["held"].shape == (2, cfg.n_experts)
    assert all("router_bias" not in layer for layer in (
        *state["params"]["layers"], state["params"]["mtp"]["block"]))
    assert "embed" not in state["params"]["mtp"] \
        and "head" not in state["params"]["mtp"]
    params = glm4_moe_lite_init(jax.random.PRNGKey(0), cfg)
    axes = glm4_moe_lite_param_axes(cfg)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    # the balanced start moved the module's bias too, and split / with undo
    # each other
    without, biases = split_bias(params, cfg)
    assert len(biases) == 2 and all(
        float(jnp.max(jnp.abs(b))) > 0 for b in biases)
    again = with_bias(without, jnp.stack(biases), cfg)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                             cfg.vocab_size)
    losses = []
    for _ in range(3):
        state, metrics = step(state, (tok, jnp.roll(tok, -1, 1)))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["loss_main"]) + 0.3 * float(metrics["loss_mtp"]),
        rel=1e-6)
    # the module's row is the last of each
    for name in ("expert_tokens", "router_prob_sum", "router_bias"):
        assert metrics[name].shape == (2, cfg.n_experts), name
    for name in ("expert_rows_held", "expert_passes"):
        assert metrics[name].shape == (2,), name
    assert int(metrics["expert_rows_held"][-1]) > 0
    np.testing.assert_array_equal(state["held"], metrics["router_bias"])


def test_a_model_without_a_module_trains_on_the_main_loss_alone():
    cfg = _tiny(n_layers=2, n_predict_layers=0, balance_tokens=0)
    params = glm4_moe_lite_init(jax.random.PRNGKey(0), cfg)
    assert "mtp" not in params
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    loss, counters = jax.jit(lambda p, b: glm4_moe_lite_loss_and_counters(
        p, b, cfg))(params, batch)
    assert "loss_mtp" not in counters
    assert float(loss) == float(counters["loss_main"])
    want = reference.reference_loss(params, *batch, cfg)
    assert float(loss) == pytest.approx(float(want), rel=TOL)


# ---------------------------------------------------------------------------
# what the family brought to models/decoder.py and ops/loss.py
# ---------------------------------------------------------------------------
def _parents_decoder_hidden(params, tokens, dec, cache=None, start_pos=None,
                            next_tokens=None):
    """models/decoder.py `decoder_hidden` as the parent commit had it,
    from before a stack could have a module behind it."""
    from ray_tpu.ops.loss import chip_views, lookup

    assert next_tokens is None

    views = None if "head" in params or cache is not None \
        else chip_views(params["embed"])
    with jax.named_scope("embed"):
        x = lookup(views, tokens) if views is not None \
            else jnp.take(params["embed"], tokens, axis=0)
        x = decoder._scaled(x, dec.embed_scale)
        if dec.hyper is not None:
            x = (x,) * dec.hyper.streams
    layers = params["layers"]
    extras = decoder._planned_extras(dec, layers, x,
                                     params["embed"].shape[0]) \
        if cache is None else ((),) * len(layers)

    @functools.cache
    def block_at(key, extra):
        block = decoder._block_of(dec, *key)
        if dec.remat is not None and cache is None:
            policy = dec.remat
            if extra or (policy is decoder.keep_kernel_outputs
                         and key[0] in decoder.KEPT_BY_KIND):
                policy = jax.checkpoint_policies.save_only_these_names(
                    *decoder._kept(key[0]), *extra)
            block = jax.checkpoint(block, policy=policy)
        return block

    per_layer, new_cache, shared = [], [], decoder.Shared()
    with jax.named_scope("layers"):
        for key, extra, layer, cache_layer in zip(
                decoder._block_keys(dec, layers), extras, layers,
                cache or [None] * len(layers)):
            x, stats, cache_layer, shared = block_at(key, extra)(
                x, layer, cache_layer, start_pos, shared)
            per_layer += [] if stats is None else [stats]
            new_cache.append(cache_layer)
    with jax.named_scope("final_norm"):
        if dec.hyper is not None:
            x = sum(t.astype(jnp.float32) for t in x).astype(x[0].dtype)
        x = decoder._scaled(decoder._norm(x, params, "lnf", dec.norm_eps),
                            dec.logit_scale)
    if views is not None:
        head = views.swapaxes(1, 2)
    else:
        head = params["head"] if "head" in params else params["embed"].T
    return x, head, per_layer, (new_cache if cache is not None else None)


def _text(lowered):
    """(a lowered program's text with no source locations, the scope paths
    its operations stand under, counted)."""
    import collections
    paths = collections.Counter(
        name for name in re.findall(r'loc\("([^"]+)"',
                                    lowered.as_text(debug_info=True))
        if "/" in name and ".py" not in name)     # op names, no frames
    return lowered.as_text().splitlines(), paths


@pytest.mark.parametrize("family", ["llama", "glm4_moe_lite_no_module"])
def test_a_stack_with_no_module_lowers_as_before(monkeypatch, family):
    """No flag says whether a stack has a module: a training forward that is
    handed no next tokens is traced and lowered, gradients and the loss's
    scan with it, to the text the parent's `decoder_hidden` and the loss
    with no mask give, the scopes with it."""
    if family == "llama":
        cfg = LlamaConfig.tiny()
        loss = lambda p, b: llama_loss(p, b, cfg)   # noqa: E731
    else:
        cfg = _tiny(dtype=jnp.bfloat16, n_predict_layers=0, balance_tokens=0)
        loss = lambda p, b: glm4_moe_lite_loss(p, b, cfg)   # noqa: E731
    params = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def lowered():
        return _text(jax.jit(jax.value_and_grad(loss)).lower(params,
                                                             (tok, tok)))

    ours, our_paths = lowered()
    for module in ("ray_tpu.models.llama", "ray_tpu.models.glm4_moe_lite"):
        monkeypatch.setattr(module + ".decoder_hidden",
                            _parents_decoder_hidden)
    parents, parents_paths = lowered()
    assert len(ours) > 200 and any("final_norm" in p for p in our_paths)
    assert not any("mtp" in p for p in our_paths)
    assert ours == parents and our_paths == parents_paths


def test_a_module_behind_several_streams_or_a_cache_is_refused(tiny):
    cfg, params, (tok, targets) = tiny
    dec = cfg.decoder(0, module=True)
    with pytest.raises(ValueError, match="several streams"):
        decoder.decoder_hidden(
            params, tok, dec._replace(hyper=decoder.HyperConnections(2)),
            next_tokens=targets)
    with pytest.raises(ValueError, match="training forward"):
        decoder.decoder_hidden(params, tok, dec, cache=init_cache(cfg, 2, 48),
                               start_pos=0, next_tokens=targets)
    # and a Decoder that does not name the module's block is told so
    with pytest.raises(ValueError, match="kinds"):
        decoder.decoder_hidden(params, tok, cfg.decoder(), next_tokens=targets)


def test_the_plan_counts_the_modules_block_and_a_second_loss():
    """`remat_plan` over the layers and the module's block, two losses: the
    module's block is one more layer of the base set and of what may be
    kept besides, and the reserve holds a second loss's working set; a
    latent block joined by the add is reckoned with the keys, values and
    cotangents its backward holds under no name."""
    cfg = _tiny(dtype=jnp.bfloat16, balance_tokens=0)
    shapes = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    layers, block = shapes["layers"], shapes["mtp"]["block"]
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), cfg.dtype)
    d, v = cfg.d_model, cfg.vocab_size
    stack = decoder.remat_plan(cfg.decoder(), layers, x, v, 2 ** 34, 0)
    both = decoder.remat_plan(cfg.decoder(0, True), [*layers, block], x, v,
                              2 ** 34, 0, losses=2)
    assert len(both.extras) == len(stack.extras) + 1 == cfg.n_layers + 1
    assert both.extras[:-1] == stack.extras
    assert both.extras[-1] == stack.extras[-1]      # an expert layer's
    assert both.reserve_bytes == stack.reserve_bytes + working_set_bytes(
        256, d, v)
    dec = cfg.decoder()
    holds = decoder._latent_holds(decoder.LATENT_ATTENTION, 256, layers[1],
                                  dec)
    assert holds == 3 * 256 * cfg.n_heads * (
        cfg.qk_head_dim + cfg.v_head_dim) * 2
    assert stack.reserve_bytes > holds
    assert decoder._latent_holds(decoder.ATTENTION, 256, layers[1], dec) == 0
    assert decoder._latent_holds(decoder.LATENT_ATTENTION, 256,
                                 {**layers[1], "hc_mlp": None}, dec) == 0
    # q is the first candidate of a latent block, not of its base set
    assert "flash_attention_q" not in decoder._kept(decoder.LATENT_ATTENTION)
    assert decoder.KEPT_WHERE_IT_FITS["flash_attention_q"] is decoder._first
    per_expert_layer = (stack.base_bytes - decoder.remat_plan(
        cfg.decoder()._replace(kinds=cfg.decoder().kinds[:-1],
                               mlp=cfg.decoder().mlp[:-1]),
        layers[:-1], x, v, 2 ** 34, 0).base_bytes)
    assert both.base_bytes == stack.base_bytes + per_expert_layer


def test_the_modules_block_is_rematerialised_as_any_layers(monkeypatch):
    """Inside a step that says what it holds, the module's block runs under
    `jax.checkpoint` with the names the plan gave it: the lowered text of
    the training forward's gradient has one rematerialised block more than
    the stack alone, and the gradients are the unrematerialised ones."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = _tiny(n_layers=2, balance_tokens=0)
    params = glm4_moe_lite_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                             cfg.vocab_size)
    batch = (tok, jnp.roll(tok, -1, 1))
    want = jax.jit(jax.grad(lambda p: glm4_moe_lite_loss(
        p, batch, dataclasses.replace(cfg, remat=False))))(params)
    got = jax.jit(jax.grad(lambda p: glm4_moe_lite_loss(p, batch, cfg)))(
        params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 1e-5)
    forward = str(jax.make_jaxpr(
        lambda p: glm4_moe_lite_loss(p, batch, cfg))(params))
    assert len(re.findall(r"\bremat2\[", forward)) == cfg.n_layers + 1


# ---------------------------------------------------------------------------
# the held layer at a quarter
# ---------------------------------------------------------------------------
def _expert_case(E=64, d=32, f=16, rows=96, k=4, key=11):
    ks = jax.random.split(jax.random.PRNGKey(key), 7)
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
        first=first, routed_scale=1.8, gated=True, weight_eps=1e-20)


@pytest.mark.parametrize("whose", ["a_main_layers", "the_modules"])
def test_the_four_quarters_and_the_shared_expert_once_are_the_uncut_layer(
        whose):
    """What ties the share to the model: experts 0-15, 16-31, 32-47, 48-63
    on four chips, each leaving out what the others' would add, and the
    shared expert, which every chip computes alike, counted once, sum to
    the reference's layer with all 64 held, times 1.8; for a main layer's
    weights and for the module's block's (its own router and bias)."""
    c = _expert_case(key=11 if whose == "a_main_layers" else 12)

    def plain(first, count):
        return reference._plain_experts(
            c["x"], c["router"], c["bias"],
            c["gate_up"][first:first + count], c["down"][first:first + count],
            c["shared_gate_up"], c["shared_down"], k=c["k"], first=first,
            scale=1.8)[0]

    uncut = plain(0, 64)
    with_shared = _held(c, 0, 16)[0]
    _close(with_shared, plain(0, 16))
    quarters = [_held(c, first, 16, shared=False)
                for first in range(0, 64, 16)]
    shared = with_shared - quarters[0][0]
    _close(sum(out for out, _ in quarters) + shared, uncut)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2
    for _, stats in quarters[1:]:
        np.testing.assert_array_equal(stats["expert_tokens"],
                                      quarters[0][1]["expert_tokens"])
    assert sum(int(s["expert_rows_held"]) for _, s in quarters) == 96 * 4


def test_counts_are_the_hand_computed_ones():
    """The cell's parameters and operations from its file, by hand.
    Parameters: attention 21,759,232 a layer; layer 0 84,677,888; an
    expert layer with 16 held 182,326,528; the module 190,721,280; table
    and head 79,298,560 each; the final norm 2,048: 1,163,304,448, the
    program's own count. Operations a token: a layer's latent-attention
    products 2 x (2048 x 768 + 768 x 5120 + 2048 x 576 + 512 x 8960 + 5120
    x 2048) = 43.5 M, its causal maps 2 x 16384 x 20 x 512 / 2 = 167.8 M;
    the dense SwiGLU 125.8 M; an expert layer's router, shared expert and
    one held row 38.0 M; the head 158.6 M; the module's projection 16.8 M:
    1,901 M forward a token, the six attention calls 67%."""
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    cfg = reference.build(config, balance_tokens=0)
    shapes = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    attention = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512
                 + 512 * 8960 + 5120 * 2048)
    expert = 3 * 2048 * 1536
    dense_layer = attention + 3 * 2048 * 10240 + 2 * 2048
    expert_layer = attention + 16 * expert + expert + 2048 * 64 + 2 * 2048
    module = 4096 * 2048 + 3 * 2048 + expert_layer
    assert (attention, expert, dense_layer, expert_layer, module) == (
        21759232, 9437184, 84677888, 182326528, 190721280)
    biases = 5 * 64
    assert count(shapes["layers"][0]) == dense_layer
    assert count(shapes["layers"][1]) == expert_layer + 64
    assert count(shapes["mtp"]) == module + 64
    assert count(shapes["embed"]) == count(shapes["head"]) == 79298560
    total = dense_layer + 4 * expert_layer + module + 2 * 79298560 + 2048
    assert total == 1163304448 and count(shapes) == total + biases
    assert "1,163,304,448" in config["reduced_from"]["num_hidden_layers"]

    products = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                    + 5120 * 2048)
    maps = 2 * 16384 * 20 * 512 / 2
    dense = 6 * 2048 * 10240
    experts = 2 * 2048 * 64 + 6 * 2048 * 1536 + 1.0 * 6 * 2048 * 1536
    head = 2 * 2048 * 38720
    project = 2 * 4096 * 2048
    forward = (5 * (products + maps) + dense + 4 * experts + head
               + (project + products + maps + experts + head))
    assert reference.forward_flops_per_token(config, 16384) == forward
    assert forward == pytest.approx(1918e6, rel=1e-3)
    assert 6 * maps / forward == pytest.approx(0.525, abs=0.005)
    assert (project + products + maps + experts + head) / forward == \
        pytest.approx(0.221, abs=0.005)
    assert reference.train_flops_per_token(cfg, 16384) == 3 * forward
    # a step's work, 16,384 tokens (the module adds none): 9.4e13
    assert 3 * forward * 16384 == pytest.approx(9.43e13, rel=5e-3)
    assert reference.held_rows_balanced(config, 16384) == 16384
    assert reference.attention_kernel_flops(config, 1, 16384) == \
        6 * 2 * 16384 ** 2 * 20 * 3 * 512 / 2
    assert reference.attention_kernel_bytes(config, 1, 16384) == \
        6 * 6 * 16384 * 20 * 512 * 2
    assert reference.expert_matmul_flops(config, 16384) == \
        5 * 9 * 2 * 16384 * 2048 * 1536
    assert reference.expert_matmul_bytes(config, 16384) == \
        5 * 9 * 2 * (16384 * (2048 + 1536) + 16 * 2048 * 1536)
    # with no module the counts are the stack's
    stack = dict(config, num_nextn_predict_layers=0)
    assert reference.forward_flops_per_token(stack, 16384) == \
        5 * (products + maps) + dense + 4 * experts + head
    assert reference.attention_kernel_flops(stack, 1, 16384) * 6 == \
        reference.attention_kernel_flops(config, 1, 16384) * 5
