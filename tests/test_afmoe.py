"""models.afmoe (Trinity-Large-Preview: gated grouped-query attention under
sandwich norms, three windowed, rotated layers to one full layer with no
positions; a dense SwiGLU layer and then a held share of SwiGLU experts
behind a sigmoid router beside a shared expert; the embedding times sqrt(d);
an untied head) against the benchmark's plain float32 reference
(chipbench/families/afmoe.py) on seeded weights, and the pieces this family
brought: two kinds of models/decoder.py's MIXERS over the one `attention`,
its gate a channel, the banded kernel calls' own scopes, and the share a
chip holds of an expert layer."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import afmoe as reference
from ray_tpu.models import afmoe as program
from ray_tpu.models import decoder
from ray_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.ops import attention as attention_ops
from ray_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "chipbench/configs/trinity-large-preview.json"
# float32 program against float32 reference: the same sums in another order
# (one masked softmax against query blocks; sorted rows against every expert
# on every token).
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
    """`params` with every norm's gain off its start: at ones a left-out
    norm would not show."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(key), len(leaves))
    return jax.tree.unflatten(tree, [
        a if a.ndim != 1 or "router_bias" in jax.tree_util.keystr(path)
        else 1.0 + 0.2 * jax.random.normal(k, a.shape)
        for k, (path, a) in zip(keys, leaves)])


@pytest.fixture(scope="module")
def tiny():
    """(the tiny config in float32: windowed, windowed, full, windowed
    under a window of 16; its seeded weights with the gains drawn apart; a
    batch of two 48-token sequences, three windows long, so that a windowed
    layer and a full one see different keys)."""
    cfg = dataclasses.replace(AfmoeConfig.tiny(), dtype=jnp.float32,
                              bias_rounds=8)
    with jax.default_matmul_precision("highest"):
        params = _drawn_apart(program.afmoe_init(jax.random.PRNGKey(0), cfg))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                             cfg.vocab_size)
    assert tok.shape[1] > 2 * cfg.sliding_window
    return cfg, params, (tok, jnp.roll(tok, -1, 1))


@pytest.fixture(scope="module")
def reference_loss(tiny):
    """The float32 reference's loss on the tiny batch, read once."""
    cfg, params, batch = tiny
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda p: reference.reference_loss(
            p, *batch, cfg))(params))


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------
def test_logits_loss_and_every_gradient_are_the_references(tiny):
    cfg, params, batch = tiny
    assert cfg.decoder().kinds == (
        "windowed_attention", "windowed_attention", "attention_nope",
        "windowed_attention")
    assert cfg.decoder().embed_scale == 8.0
    _close(jax.jit(lambda p: program.afmoe_forward(p, batch[0], cfg))(params),
           jax.jit(lambda p: reference.reference_logits(
               p, batch[0], cfg))(params))
    want, dwant = jax.jit(jax.value_and_grad(
        lambda p: reference.reference_loss(p, *batch, cfg)))(params)
    got, dgot = jax.jit(jax.value_and_grad(
        lambda p: program.afmoe_loss(p, batch, cfg)))(params)
    assert abs(float(got) - float(want)) <= TOL * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(dwant))
    seen = 0
    for path, g in jax.tree_util.tree_leaves_with_path(dgot):
        if "router_bias" in jax.tree_util.keystr(path):
            continue                        # no gradient reaches it
        assert float(jnp.max(jnp.abs(g))) > 0, path
        _close(g, flat_want[path])
        seen += 1
    # every leaf: four layers' six attention weights and four norms, a dense
    # layer's three, three expert layers' five, three outside
    assert seen == 4 * (6 + 4) + 3 + 3 * 5 + 3


def test_prefill_then_decode_through_the_cache_is_the_full_forward(tiny):
    """With a cache the window is the read's mask (`_cache_mask`), the
    full layers stay unrotated and the gate is applied as in training."""
    cfg, params, batch = tiny
    tok = batch[0]
    want = jax.jit(lambda p: reference.reference_logits(
        p, tok[:, :43], cfg))(params)
    cache = init_cache(cfg, 2, 64)
    assert [sorted(layer) for layer in cache] == [["k", "v"]] * 4
    assert cache[0]["k"].shape == (2, cfg.n_kv_heads, 64, cfg.head_dim)
    # a prefill of 40 (two and a half windows), then three tokens
    forward = jax.jit(lambda p, toks, cache, at: cached_forward(
        p, toks, cache, at, cfg))
    got, cache = forward(params, tok[:, :40], cache, 0)
    for i in range(40, 43):
        step, cache = forward(params, tok[:, i:i + 1], cache, i)
        got = jnp.concatenate([got, step], axis=1)
    _close(got, want)


def test_train_step_keeps_the_biases_and_reports_the_counters(tiny):
    cfg, _, batch = tiny
    init_state, step = program.make_afmoe_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    assert state["held"].shape == (3, cfg.n_experts)
    assert "router_bias" not in state["params"]["layers"][1]
    state, m = step(state, batch)
    tokens = batch[0].size
    assert m["expert_tokens"].shape == (3, cfg.n_experts)
    assert (np.asarray(m["expert_tokens"]).sum(-1)
            == cfg.experts_per_token * tokens).all()
    assert m["expert_rows_held"].shape == (3,)
    assert float(m["expert_load_max_over_mean"]) >= 1.0
    np.testing.assert_array_equal(state["held"], m["router_bias"])
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# the configuration's file, recounted from the program
# ---------------------------------------------------------------------------
def _file():
    with open(os.path.join(ROOT, CONFIG)) as f:
        return json.load(f)


def test_the_file_states_the_parameters_the_program_holds():
    """1,603,993,856 parameters = 12.83 GB at this repo's 8 bytes a
    parameter: layer 0 176,173,312, an expert layer 318,517,504 (8 of 256
    experts held), table and head 76,873,728 each, the final norm."""
    config = _file()
    cfg = reference.build(config)
    shapes = jax.eval_shape(lambda: program.split_bias(
        program.afmoe_init(jax.random.PRNGKey(0), dataclasses.replace(
            cfg, balance_tokens=0)), cfg)[0])

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    layers = [count(layer) for layer in shapes["layers"]]
    assert layers == [176_173_312] + [318_517_504] * 4
    assert count(shapes["embed"]) == count(shapes["head"]) == 76_873_728
    assert count(shapes) == config["parameters"] == 1_603_993_856
    assert 12.83e9 <= count(shapes) * 8 <= 12.84e9
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.held, cfg.n_experts,
            cfg.experts_per_token, cfg.vocab_size, cfg.sliding_window,
            cfg.routed_scale) == (5, 1, (0, 8), 256, 4, 25024, 4096, 2.448)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL, SLIDING)


def test_the_file_has_every_catalog_key_at_its_value_but_the_four_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    config = _file()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert set(config["reduced_from"]) == set(config["reduced"])
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    sizes = config["deployment_sizes"]
    assert {k: sizes[k] for k in config["reduced"]} == {
        k: row["config"][k] for k in config["reduced"]}
    assert (sizes["chips_sharing_a_layer"], sizes["experts_a_chip"],
            sizes["chips_sharing_the_vocabulary"]) == (32, 8, 8)
    assert config["vocab_size"] * 8 == sizes["vocab_size"]
    assert {"assumed", "departures", "deployment"} <= set(config)
    assert "TO BE FILLED" not in json.dumps(config)


def test_the_bands_work_is_counted_by_attention_plans_own_rule():
    """75% of the triangle at two windows; the family's count, on Python
    ints and with no jax, is `AttentionPlan.required_pairs`."""
    for seq, window in ((8192, 4096), (8192, None), (6144, 4096),
                        (2048, 4096)):
        plan = attention_ops.attention_plan(seq, 128, True, jnp.bfloat16,
                                            window)
        assert reference.causal_pairs(seq, window) == plan.required_pairs
    assert reference.causal_pairs(8192, 4096) == 25_167_872
    assert reference.causal_pairs(8192, 4096) / reference.causal_pairs(
        8192) == pytest.approx(0.75, abs=1e-3)
    config = _file()
    assert reference.window_attention_flops(config, 1, 8192) \
        == 4 * 6 * 2 * 25_167_872 * 48 * 128
    assert reference.attention_kernel_flops(config, 1, 8192) \
        == reference.window_attention_flops(config, 1, 8192) \
        + 6 * 2 * reference.causal_pairs(8192) * 48 * 128
    # a held expert sees a thirty-second of its deployment's rows
    assert reference.held_rows_balanced(config, 8192) == 1024


# ---------------------------------------------------------------------------
# the share a chip holds
# ---------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """Expert parallelism without its exchange: the routed parts of all
    the chips' shares (each `held_moe_layer` told which experts it holds,
    routing over all of them), with the shared expert, which every chip
    computes alike, counted once, are what the uncut reference gives for
    the whole layer. No new routing code: the arguments the layer has."""
    cfg, params, _ = tiny
    lay = params["layers"][1]
    d, E, f, held = cfg.d_model, cfg.n_experts, cfg.d_expert, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (96, d))
    gate_up = jax.random.normal(ks[1], (E, d, 2 * f)) * d ** -0.5
    down = jax.random.normal(ks[2], (E, f, d)) * f ** -0.5
    bias = 0.1 * jax.random.normal(ks[3], (E,))
    sizes = dict(experts_per_token=cfg.experts_per_token,
                 routed_scale=cfg.routed_scale, weight_eps=1e-20, gated=True)
    shared = (lay["shared_gate_up"], lay["shared_down"])
    shared_alone = moe.held_moe_layer(
        x, lay["router"], jnp.full((E,), -10.0).at[E - 3:].set(10.0),
        gate_up[:1], down[:1], *shared, first=0, **sizes)[0]
    parts, rows = [], 0
    for first in range(0, E, held):
        out, stats = moe.held_moe_layer(
            x, lay["router"], bias, gate_up[first:first + held],
            down[first:first + held], *shared, first=first, **sizes)
        parts.append(out - shared_alone)
        rows += int(stats["expert_rows_held"])
    assert len(parts) == E // held == 4
    assert rows == 96 * cfg.experts_per_token   # every assignment held once
    want, _ = reference._plain_experts(
        x, lay["router"], bias, gate_up, down, *shared,
        k=cfg.experts_per_token, first=0, scale=cfg.routed_scale)
    _close(sum(parts) + shared_alone, want)
    # and a share alone is no small part of it: the test compares something
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.05 * float(
        jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# planted faults: each moves the loss and its own layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fault", sorted(reference.STRUCTURAL_FAULTS))
def test_a_planted_fault_moves_the_loss_and_its_layer(tiny, reference_loss,
                                                      fault):
    """`planted` puts the fault where the program finds it (through the
    module's own name at the time of the call): the loss leaves the
    reference's by more than 5e-5 (the program's own distance is 0 at
    this size; the smallest, the scale left out, moves it 9e-5) and the
    fault's own groups of `kernel_errors` read over 0.2, where the program
    reads under 1e-5; afterwards the real functions are back."""
    cfg, params, batch = tiny
    want = reference_loss
    with reference.planted(fault):
        got = float(jax.jit(lambda p: program.afmoe_loss(
            p, batch, cfg))(params))
        errors = reference.kernel_errors(cfg, 0)
    groups = reference.STRUCTURAL_FAULTS[fault][3]
    assert {name.split("_")[0] for name in errors} == set(groups)
    assert abs(got - want) > 5e-5, (fault, got - want)
    assert max(errors.values()) > 0.2, (fault, errors)
    assert getattr(decoder, reference.STRUCTURAL_FAULTS[fault][1]).__module__ \
        .startswith("ray_tpu."), "the real function is not back"


def test_the_program_is_inside_the_reference_where_no_fault_is_planted(tiny):
    cfg = tiny[0]
    errors = reference.kernel_errors(cfg, 0)
    assert {name.split("_")[0] for name in errors} == set(reference.GROUPS)
    assert max(errors.values()) < 1e-5, errors
    low = reference.kernel_errors(cfg, 0, low=True)
    assert set(low) == set(errors) and max(low.values()) > 1e-3


# ---------------------------------------------------------------------------
# the two new kinds over the one attention
# ---------------------------------------------------------------------------
def _parents_attention(x, layer, dec, cache=None, start_pos=None):
    """`decoder.attention` as the parent of PR 65 had it: no window, no
    gate."""
    b, L, d = x.shape
    h, kvh, hd = dec.n_heads, dec.n_kv_heads, dec.head_dim
    _, q, k, v, sp, _ = decoder._qkv_heads(x, layer, dec, cache, start_pos)
    attn = decoder.flash_attention(
        q, decoder._across_group(k, h // kvh),
        decoder._across_group(v, h // kvh), True, dec.sm_scale)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, L, h * hd)
    return jnp.einsum("bsd,de->bse", attn, layer["wo"]), None


def _strip(text):                   # source locations are all that may differ
    lines = (re.sub(r"\s*loc\(.*\)$", "", line)
             for line in text.splitlines() if not line.startswith("#loc"))
    return [line for line in lines if line.strip()]


def _plain_attention_families():
    from ray_tpu import models
    from ray_tpu.models.keye_vl2 import KeyeVL2Config
    return {"gpt": models.GPTConfig, "llama": models.LlamaConfig,
            "moe": models.MoEConfig, "lfm2_moe": models.Lfm2MoeConfig,
            "keye_vl2": KeyeVL2Config}


@pytest.mark.parametrize("family", ["gpt", "llama", "moe", "lfm2_moe",
                                    "keye_vl2"])
def test_a_layer_that_holds_no_gate_lowers_as_before(family):
    """What a layer holds and what its kind's row says decide what
    `attention` does: the plain `attention` kind of the four families that
    run it (and the projections the sparse kind shares with it), whose
    layers hold no `attn_gate` and whose row is not windowed, is traced and
    lowered to the text the parent's function gives, kernels' scopes with
    it."""
    cfg = _plain_attention_families()[family].tiny()
    dec = cfg.decoder()
    index = next(i for i, kind in enumerate(dec.kinds)
                 if kind in (decoder.ATTENTION, decoder.SPARSE_ATTENTION))
    layer = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"][index]
    assert "attn_gate" not in layer
    assert not decoder.MIXERS[dec.kinds[index]].windowed
    x = jax.ShapeDtypeStruct((2, 128, cfg.d_model), cfg.dtype)

    def lowered(fn):
        return jax.jit(lambda x, layer: fn(x, layer, dec)[0]).lower(
            x, layer).as_text(debug_info=True)

    ours, parents = lowered(decoder.attention), lowered(_parents_attention)
    ours, parents = _strip(ours), _strip(parents)
    assert not [line for line in ours
                if "attention_gate" in line or "_window" in line]
    assert len(ours) > 30 and ours == parents


def test_the_banded_calls_have_scopes_of_their_own(monkeypatch):
    """A windowed call's three kernels lower under `flash_attention_fwd_
    window`, `_dq_window`, `_dkv_window`; a full call's under the names
    they had, which the banded names hold as a substring (the readers of
    phi4flash-train-1chip's three kernel metrics match by substring and
    read what they read)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.float32)

    def text(window):
        def loss(q, k, v):
            return jnp.sum(attention_ops.flash_attention(
                q, k, v, True, None, window))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text(debug_info=True)

    banded, full = text(128), text(None)
    for scope in ("flash_attention_fwd", "flash_attention_dq",
                  "flash_attention_dkv"):
        assert f"{scope}_window" in banded and f"{scope}_window" not in full
        assert scope in full
        assert scope in f"{scope}_window"
    from ray_tpu.util import profiling
    assert all(name in profiling.DEVICE_SCOPES
               for name in reference.WINDOW_KERNEL_ROWS)
    assert decoder.MIXER_SCOPES[decoder.WINDOWED_ATTENTION] \
        == "windowed_attention_mixer"
    assert decoder.MIXER_SCOPES[decoder.ATTENTION_NOPE] == "attention_mixer"


def test_a_gated_gqa_block_keeps_its_kernels_output_and_no_q_k_or_v(tiny):
    """`remat_plan` on the tiny stack: both new kinds keep out and lse (and
    the router's scores) and none of q, k, v; their candidates are the two
    projections, the gate's, k at kv-head width and q; with room every
    attention block takes all five."""
    cfg, params, batch = tiny
    dec = cfg.decoder()
    for kind in (decoder.WINDOWED_ATTENTION, decoder.ATTENTION_NOPE):
        kept = decoder._kept(kind)
        assert {"flash_attention_out", "flash_attention_lse"} <= set(kept)
        assert not {"flash_attention_q", "flash_attention_k",
                    "flash_attention_v"} & set(kept)
        assert set(decoder._fits(kind)) - set(
            decoder.KEPT_WHERE_IT_FITS) == {
            "attention_q_proj", "attention_kv_proj", "attention_gate_proj",
            "attention_k_heads"}
        assert "flash_attention_q" in decoder._fits(kind)
    x = jax.ShapeDtypeStruct((2, 48, cfg.d_model), cfg.dtype)
    none = decoder.remat_plan(dec, params["layers"], x, cfg.vocab_size,
                              None, None)
    assert none.extras == ((),) * 4 and none.kept_extra_bytes == 0
    roomy = decoder.remat_plan(dec, params["layers"], x, cfg.vocab_size,
                               2 ** 31, 10 ** 6)
    assert roomy.base_bytes == none.base_bytes
    for names in roomy.extras:
        assert {"attention_q_proj", "attention_kv_proj",
                "attention_gate_proj"} <= set(names)
