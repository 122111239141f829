"""models.decoder.MIXERS: a layer's kind, named by its family's config,
picks its sequence mixer, its state and its block's branches in one table; a cache is made from
that table and the shapes of the weights alone; and the seven names the
benchmark calls on the chip (chipbench/families/*.py `hold_kernels`,
`planted`) are there, under their signatures, and are what the program
calls when it is traced."""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import decoder, generate
from ray_tpu.models.generate import init_cache, make_continuous_fns
from ray_tpu.models.gpt import GPTConfig, gpt_loss
from ray_tpu.models.hybrid import HybridConfig, hybrid_loss
from ray_tpu.models.lfm2_moe import Lfm2MoeConfig, lfm2_moe_loss
from ray_tpu.models.llama import LlamaConfig, llama_loss
from ray_tpu.models.moe import MoEConfig, moe_loss
from ray_tpu.models.nemotron_h import NemotronHConfig, nemotron_h_loss
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig, olmo_hybrid_loss
from ray_tpu.models.sambay import SambaYConfig, sambay_loss
from ray_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                          glm4_moe_lite_loss)
from ray_tpu.models.keye_vl2 import KeyeVL2Config, keye_vl2_loss
from ray_tpu.models.xing4 import Xing4Config, xing4_loss
from ray_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                           bailing_hybrid_loss)
from ray_tpu.models.afmoe import AfmoeConfig, afmoe_loss

FAMILIES = {
    "gpt": (GPTConfig, gpt_loss),
    "llama": (LlamaConfig, llama_loss),
    "moe": (MoEConfig, moe_loss),
    "hybrid": (HybridConfig, hybrid_loss),
    "lfm2_moe": (Lfm2MoeConfig, lfm2_moe_loss),
    "sambay": (SambaYConfig, sambay_loss),
    "olmo_hybrid": (OlmoHybridConfig, olmo_hybrid_loss),
    "nemotron_h": (NemotronHConfig, nemotron_h_loss),
    "xing4": (Xing4Config, xing4_loss),
    "glm4_moe_lite": (Glm4MoeLiteConfig, glm4_moe_lite_loss),
    "keye_vl2": (KeyeVL2Config, keye_vl2_loss),
    "bailing_hybrid": (BailingHybridConfig, bailing_hybrid_loss),
    "afmoe": (AfmoeConfig, afmoe_loss),
}
KINDS = (decoder.ATTENTION, decoder.MAMBA2, decoder.MAMBA1,
         decoder.GATED_DELTA, decoder.GMU, decoder.DIFF_WINDOWED,
         decoder.DIFF_FULL, decoder.DIFF_CROSS, decoder.ATTENTION_ONLY,
         decoder.MAMBA2_ONLY, decoder.EXPERTS, decoder.SHORT_CONV,
         decoder.LATENT_ATTENTION, decoder.SPARSE_ATTENTION, decoder.KDA,
         decoder.WINDOWED_ATTENTION, decoder.ATTENTION_NOPE)
STATELESS = (decoder.GMU, decoder.DIFF_CROSS, decoder.EXPERTS)


@pytest.fixture(params=sorted(FAMILIES))
def family(request):
    """(config at the CPU tests' size in float32, its loss)."""
    config, loss = FAMILIES[request.param]
    return dataclasses.replace(config.tiny(), dtype=jnp.float32), loss


def test_the_table_has_the_seventeen_kinds_and_the_tiny_models_run_them_all():
    assert set(decoder.MIXERS) == set(KINDS) and len(set(KINDS)) == 17
    run = {kind for config, _ in FAMILIES.values()
           for kind in config.tiny().decoder().kinds}
    assert run == set(KINDS)
    for kind in STATELESS:
        assert decoder.MIXERS[kind].state(None, {}, 2, 8, jnp.float32) == {}


def test_a_row_says_which_branches_its_block_has():
    """Sequence mixer, channel mixer or both, by the row and never by the
    weights: the three single-branch kinds are Nemotron-H's, every other
    block has both, and a single-branch row shares its sequence mixer and
    its state with the two-branch kind it is named after."""
    branches = {kind: (row.apply is not None, row.channel)
                for kind, row in decoder.MIXERS.items()}
    alone = {decoder.ATTENTION_ONLY: (True, False),
             decoder.MAMBA2_ONLY: (True, False),
             decoder.EXPERTS: (False, True)}
    assert {k: v for k, v in branches.items() if v != (True, True)} == alone
    for one, both in ((decoder.ATTENTION_ONLY, decoder.ATTENTION),
                      (decoder.MAMBA2_ONLY, decoder.MAMBA2)):
        assert decoder.MIXERS[one][:2] == decoder.MIXERS[both][:2]
    assert NemotronHConfig.tiny().decoder().kinds == (
        decoder.MAMBA2_ONLY, decoder.EXPERTS, decoder.MAMBA2_ONLY,
        decoder.ATTENTION_ONLY, decoder.EXPERTS)


def test_a_layers_channel_mixer_is_named_as_its_sequence_mixer_is(family):
    """`Decoder.mlp` is one callable a layer, as long as `kinds`, built by
    the family's `decoder()` from the fields it has: the one function a
    layer in seven families; LFM2's, Xing4's and GLM-4.7-Flash's dense
    SwiGLU in their leading layers and one expert layer's function in all
    the others, a prediction module's block named last where one is asked
    for."""
    cfg, _ = family
    dec = cfg.decoder()
    assert isinstance(dec.mlp, tuple) and len(dec.mlp) == len(dec.kinds)
    assert all(callable(mlp) for mlp in dec.mlp)
    if isinstance(cfg, Glm4MoeLiteConfig):
        with_module = cfg.decoder(module=True)
        assert with_module.kinds[:-1] == dec.kinds and [
            getattr(m, "func", m) for m in with_module.mlp[:-1]] == [
            getattr(m, "func", m) for m in dec.mlp]
        assert with_module.mlp[-1] is with_module.mlp[-2]   # an expert layer's
        assert with_module._replace(mlp=dec.mlp) == dec._replace(
            kinds=with_module.kinds)
    if isinstance(cfg, (Lfm2MoeConfig, Xing4Config, Glm4MoeLiteConfig,
                        BailingHybridConfig, AfmoeConfig)):
        dense = cfg.n_dense_layers
        assert 0 < dense < cfg.n_layers
        assert set(dec.mlp[:dense]) == {decoder.swiglu_mlp}
        experts, = set(dec.mlp[dense:])
        assert experts.func is decoder.held_gated_experts
        assert experts.keywords["weight_eps"] == (
            1e-6 if isinstance(cfg, Lfm2MoeConfig) else 1e-20)
    else:
        assert len(set(dec.mlp)) == 1


def test_a_channel_mixer_too_few_is_refused_by_name():
    cfg = Lfm2MoeConfig.tiny()
    params = cfg.init(jax.random.PRNGKey(0))
    dec = cfg.decoder()
    dec = dec._replace(mlp=dec.mlp[:-1])
    with pytest.raises(ValueError, match="3 channel mixers"):
        decoder.decoder_hidden(params, jnp.zeros((2, 8), jnp.int32), dec)


@pytest.mark.parametrize("kind,norm,held", [
    (decoder.MAMBA2_ONLY, "ln1", "in_proj"),
    (decoder.ATTENTION_ONLY, "ln1", "wq"),
    (decoder.EXPERTS, "ln2", "router")])
def test_a_single_branch_block_is_x_plus_its_one_mixer(kind, norm, held):
    """x + mixer(norm(x)) and nothing else: a layer of such a kind holds
    one norm and its mixer's weights, its block is handed no channel
    mixer (or has no sequence mixer to run), and zeroing the branch's
    output matrix gives back x."""
    cfg = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32)
    dec = cfg.decoder()
    index = dec.kinds.index(kind)
    layer = cfg.init(jax.random.PRNGKey(0))["layers"][index]
    assert norm in layer and held in layer
    assert not {"ln1", "ln2"} - {norm} & set(layer)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    # a sequence-only block is handed no channel mixer and calls none
    mlp = dec.mlp[index] if kind == decoder.EXPERTS else None
    out, stats, cache, _ = decoder._block(
        x, layer, None, None, dec=dec, kind=kind, mlp=mlp)
    assert out.shape == x.shape and cache is None
    assert (stats is not None) == (kind == decoder.EXPERTS)
    assert float(jnp.max(jnp.abs(out - x))) > 1e-3
    silent = {**layer, **{name: jnp.zeros_like(layer[name]) for name in
                          ("out_proj", "wo", "expert_down", "shared_down")
                          if name in layer}}
    same = decoder._block(x, silent, None, None, dec=dec, kind=kind,
                          mlp=mlp)[0]
    assert jnp.array_equal(same, x)


def test_a_layer_holds_what_its_kinds_mixer_reads(family):
    """One kind a layer, and every layer `cfg.init` makes goes through its
    row's `apply` (shapes only): a missing weight is a KeyError here."""
    cfg, _ = family
    dec = cfg.decoder()
    layers = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"]
    assert len(dec.kinds) == len(layers) == cfg.n_layers
    assert set(dec.kinds) <= set(decoder.MIXERS)

    def stack(x, layers):
        shared = decoder.Shared()
        for i, (kind, layer) in enumerate(zip(dec.kinds, layers)):
            row = decoder.MIXERS[kind]
            if row.apply is None:           # a block of the channel mixer
                continue
            y, cache, shared, *stats = row.apply(
                x, layer, dec, None, None, shared, i,
                dec.window if row.windowed else None)
            assert bool(stats) == (kind in (decoder.SPARSE_ATTENTION,
                                            decoder.KDA)), kind
            assert cache is None and y.shape == x.shape, kind
            assert (shared.k is not None) == (
                decoder.DIFF_FULL in dec.kinds[:i + 1]), kind
        return x

    jax.eval_shape(stack, jax.ShapeDtypeStruct((2, 16, cfg.d_model),
                                               cfg.dtype), layers)


def test_a_cache_from_shapes_is_the_cache_from_weights(family):
    """`init_cache` makes no weight; on real parameters `empty_cache`
    gives the same leaves, and every leaf has the batch first."""
    cfg, _ = family
    b, n = 3, 24
    params = cfg.init(jax.random.PRNGKey(0))
    want = decoder.empty_cache(cfg.decoder(), params["layers"], b, n,
                               cfg.dtype)
    got = init_cache(cfg, b, n)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert len(got) == cfg.n_layers
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert g.shape[0] == b and not g.any()
    for kind, state in zip(cfg.decoder().kinds, got):
        assert bool(state) == (kind not in STATELESS)


def test_a_slot_of_the_shared_cache_is_the_request_alone(family):
    """What the batch-first rule is for: a prompt prefilled into slot 1 of
    three (axis 0 of every leaf cut out and written back) gives the
    logits it gives alone, and leaves the other slots empty."""
    cfg, _ = family
    n, slots, length = 32, 3, 16
    params = cfg.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, length), 0,
                                cfg.vocab_size)
    insert, _ = make_continuous_fns(cfg, n, slots)
    last, cache = insert(params, prompt, init_cache(cfg, slots, n), 1,
                         length)
    alone, _ = generate.cached_forward(params, prompt, init_cache(cfg, 1, n),
                                       0, cfg)
    assert jnp.allclose(last, alone[0, -1], atol=1e-5)
    for leaf in jax.tree.leaves(cache):
        assert not leaf[0].any() and not leaf[2].any()
        assert leaf[1].any()


@pytest.mark.parametrize("entry", ["decoder_hidden", "empty_cache"])
@pytest.mark.parametrize("fault", ["unknown_kind", "wrong_length"])
def test_kinds_that_do_not_fit_are_refused_by_name(entry, fault):
    cfg = HybridConfig.tiny()
    params = cfg.init(jax.random.PRNGKey(0))
    kinds = cfg.decoder().kinds
    kinds = (("latent",) + kinds[1:] if fault == "unknown_kind"
             else kinds + (decoder.ATTENTION,))
    dec = cfg.decoder()._replace(kinds=kinds)
    given = "latent" if fault == "unknown_kind" else "3 layers"
    with pytest.raises(ValueError, match=given) as refused:
        if entry == "empty_cache":
            decoder.empty_cache(dec, params["layers"], 2, 8, cfg.dtype)
        else:
            decoder.decoder_hidden(params, jnp.zeros((2, 8), jnp.int32), dec)
    assert str(kinds) in str(refused.value)


@pytest.mark.parametrize("n_layers", [4, 8, 12, 32])
def test_sambay_windows_what_a_mamba1_layer_still_follows(n_layers):
    """The rule the stack used to read off the weights, now the config's:
    a differential layer of its own keys is windowed exactly while a
    Mamba-1 layer follows it, the one after the last hands its keys and
    values on, and every later one reads them."""
    kinds = SambaYConfig.tiny(n_layers).decoder().kinds
    last_scan = max(i for i, k in enumerate(kinds) if k == decoder.MAMBA1)
    for i, kind in enumerate(kinds):
        row = decoder.MIXERS[kind]
        if kind in (decoder.MAMBA1, decoder.GMU):
            assert (kind == decoder.MAMBA1) == (i <= last_scan)
            assert not (row.windowed or row.hands_on_kv or row.reads_index)
            continue
        assert row.reads_index
        assert row.windowed == (i < last_scan)
        assert row.hands_on_kv == (i == last_scan + 1)
        assert (kind == decoder.DIFF_CROSS) == (i > last_scan + 1)


# name on ray_tpu.models.decoder -> (what the benchmark hands it by position,
# under the benchmark's names for them; the family whose tiny program calls
# it)
FROZEN = {
    "ssm_scan": (("x", "dt", "a", "B", "C", "D", "chunk", "init"), "hybrid"),
    "selective_scan": (("x", "dt", "A", "B", "C", "D", "init"), "sambay"),
    "flash_attention": (("q", "k", "v", "causal", "sm_scale"), "gpt"),
    "gated_delta_rule": (("q", "k", "v", "g", "beta", "chunk", "init"),
                         "olmo_hybrid"),
    "_unit_heads": (("t", "heads", "scale", "eps"), "olmo_hybrid"),
    "gmu": (("x", "layer", "dec", "m"), "sambay"),
    "differential_maps": (("q", "k", "v", "layer", "dec", "index", "window"),
                          "sambay"),
    "gated_short_conv": (("bcx", "weight", "tail"), "lfm2_moe"),
    "head_rms_norm": (("t", "weight", "eps"), "lfm2_moe"),
    "latent_attention": (("x", "layer", "dec"), "xing4"),
    "hyper_connection": (("streams", "hc", "hyper"), "xing4"),
    "_streams_read": (("x", "hc", "hyper"), "xing4"),
    "prediction_module": (("h", "embedded", "module", "block", "eps"),
                          "glm4_moe_lite"),
    # chipbench/families/keye_vl2.py's: the mixer and the passes it calls
    "sparse_attention": (("x", "layer", "dec"), "keye_vl2"),
    "_detached": (("y",), "keye_vl2"),
    "_index_heads": (("y", "layer", "dec", "positions"), "keye_vl2"),
    "layer_norm": (("x", "weight", "bias", "eps"), "keye_vl2"),
    "index_scores": (("q", "k", "w"), "keye_vl2"),
    "select": (("scores", "topk"), "keye_vl2"),
    "attention_and_lse": (("q", "k", "v", "sm_scale", "selected"),
                          "keye_vl2"),
    "indexer_loss": (("q_index", "k_index", "w", "scores", "selected", "q",
                      "k", "lse", "sm_scale"), "keye_vl2"),
    # chipbench/families/bailing_hybrid.py's: the rule and the mixer
    # (`held_moe_layer`, which it hands the group limit by keyword, and
    # `_unit_heads` and `latent_attention` are above)
    "kda_rule": (("q", "k", "v", "g", "beta", "chunk", "init",
                  "lower_bound"), "bailing_hybrid"),
    "kda": (("x", "layer", "dec"), "bailing_hybrid"),
}


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_the_program_calls_the_frozen_name_through_the_module(name,
                                                              monkeypatch):
    """The benchmark's `planted` swaps the name on the module and traces
    the program; `hold_kernels` calls it by position. Both happen on the
    chip only. Here: the name is there, takes those positions, and a swap
    is seen by a program traced after it."""
    parameters, family = FROZEN[name]
    real = getattr(decoder, name)
    inspect.signature(real).bind(*parameters)      # by position, that many
    config, loss = FAMILIES[family]
    cfg = config.tiny()
    seen = []

    def swapped(*args, **kwargs):
        seen.append(len(args))
        return real(*args, **kwargs)

    monkeypatch.setattr(decoder, name, swapped)
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    jax.eval_shape(lambda params, tok: loss(params, (tok, tok), cfg),
                   jax.eval_shape(cfg.init, jax.random.PRNGKey(0)), tok)
    assert seen and min(seen) >= len(parameters), (name, seen)


def test_generate_names_no_family():
    import ray_tpu.models as models
    families = {getattr(models, name) for name in (
        "gpt", "llama", "moe", "hybrid", "sambay", "olmo_hybrid",
        "nemotron_h", "lfm2_moe", "xing4", "glm4_moe_lite", "keye_vl2",
        "bailing_hybrid")}
    held = {v for v in vars(generate).values() if inspect.ismodule(v)}
    assert not held & families
    assert "cache_layers" not in inspect.getsource(generate)
