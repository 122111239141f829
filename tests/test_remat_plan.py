"""models.decoder.remat_plan: which names of KEPT_WHERE_IT_FITS each layer
of a rematerialised stack keeps beyond KEPT_UNDER_REMAT, as a pure function
of shapes. At the cells' own shapes (abstractly: nothing is computed and no
kernel lowered, so the published widths cost a few seconds on the CPU), at
the edges (no capacity, no state, growing capacity), and, on a small
Mamba-2 + experts decoder, that a step under an extended policy is the base
policy's step to float32 rounding. What XLA makes of each cell's plan is
tests/test_compile_v5e_*.py's."""

import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder
from ray_tpu.models.nemotron_h import (NemotronHConfig, nemotron_h_init,
                                       nemotron_h_loss)
from ray_tpu.ops import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_BYTES = int(15.75 * 2 ** 30)      # a v5e's `bytes_limit`


def _load(rel):
    with open(os.path.join(ROOT, "chipbench", rel)) as f:
        return json.load(f)


def _cell(monkeypatch, family, config, traffic):
    """(dec, the layers' shapes, x, vocabulary rows, state bytes) of a
    train cell as its step sees them, the kernels the chip's (theirs are
    the names the base set keeps)."""
    from ray_tpu.models._training import step_state_bytes

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    mix = _load(f"traffic/{traffic}.json")
    cfg = family.build(_load(f"configs/{config}.json"), remat=True)
    _, init_state, _, _ = family.train_program(cfg)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
    dec = cfg.decoder()
    x = jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq"], cfg.d_model), cfg.dtype)
    # the layers as the loss hands them to the stack (a router's bias, which
    # the optimizer does not own, is back among its layer's weights)
    params = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    return (dec, params["layers"], x, params["embed"].shape[0],
            step_state_bytes(state))


@pytest.fixture
def nemotron(monkeypatch):
    from chipbench.families import nemotron_h
    return _cell(monkeypatch, nemotron_h, "nemotron-3-nano-30b-a3b",
                 "pretrain-nemotron3nano-b1-s16384")


@pytest.fixture
def granite(monkeypatch):
    from chipbench.families import granite_hybrid
    return _cell(monkeypatch, granite_hybrid, "granite-4.0-h-micro",
                 "pretrain-granite4h-b1-s16384")


@pytest.fixture
def keye(monkeypatch):
    from chipbench.families import keye_vl2
    return _cell(monkeypatch, keye_vl2, "keye-vl-2.0-30b-a3b",
                 "pretrain-keyevl2-b1-s16384")


def test_nemotrons_cell_keeps_every_candidate_of_its_four_mamba_layers(
        nemotron):
    """MEMEM*EME at 16,384 tokens beside 7.90 GB of state on a v5e: the
    four Mamba-2 layers keep their input projection ([16384, 10304]
    bfloat16, 337,641,472 bytes a layer) and their gated norm's output
    ([16384, 4096], 134,217,728), the four expert layers their routing's
    choices and the shared expert's float32 up projection ([16384, 3712],
    243,269,632), the attention layer nothing: it has no candidate."""
    dec, layers, x, vocab, state_bytes = nemotron
    assert state_bytes == 7_901_399_048
    plan = decoder.remat_plan(dec, layers, x, vocab, V5E_BYTES, state_bytes)
    mamba = ("ssm_gated", "ssm_in_proj")
    experts = ("moe_choice", "moe_shared_up")
    assert plan.extras == (mamba, experts, mamba, experts, mamba, (),
                           experts, mamba, experts)
    assert plan.layers_extended == 8
    choices = 3 * 16384 * 6 * 4 + 16 * 4
    assert plan.kept_extra_bytes == 4 * (
        337_641_472 + 134_217_728 + 243_269_632 + choices)
    assert plan.capacity == V5E_BYTES and plan.state_bytes == state_bytes
    assert plan.bytes_left == (
        V5E_BYTES - decoder._UNDER_CAPACITY - state_bytes - plan.base_bytes
        - plan.reserve_bytes - plan.kept_extra_bytes) > 0
    # the order is cost saved a byte kept: with a GiB less the choices (a
    # sort's passes for under a megabyte) and the projections (2,688 flops
    # a byte) stay, then the four float32 shared projections (1,344) fit
    # (three while the attention layer's lse was padded to 128 lanes), and
    # none of the norms' outputs (1,200) in what is left
    less = decoder.remat_plan(dec, layers, x, vocab,
                              V5E_BYTES - 2 ** 30, state_bytes)
    assert less.extras == (("ssm_in_proj",), experts) * 2 + (
        ("ssm_in_proj",), (), experts, ("ssm_in_proj",), experts)
    assert 0 <= less.bytes_left < 134_217_728


def test_granites_cell_keeps_some_and_not_all(granite):
    """Nine Mamba-2 layers and 100,352 vocabulary rows: what the loss's
    chunk leaves (1.58 GB; 1.31 while the attention layer's lse was padded
    to 128 lanes) holds the first layer's MLP gate and up (536,870,912
    bytes) and its input projection (278,921,216), then the second's gate
    and up, which stands before its input projection at the same 2,048
    flops a byte and fits now, and the first's gated norm's output
    (134,217,728, at 1,200), where Nemotron's cell, with a sixth of the
    vocabulary, keeps every candidate."""
    dec, layers, x, vocab, state_bytes = granite
    plan = decoder.remat_plan(dec, layers, x, vocab, V5E_BYTES, state_bytes)
    assert plan.extras == (("mlp_gate_up", "ssm_gated", "ssm_in_proj"),
                           ("mlp_gate_up",)) + ((),) * 8
    assert plan.layers_extended == 2
    assert plan.kept_extra_bytes == 2 * 536_870_912 + 278_921_216 + 134_217_728
    assert plan.bytes_left < 134_217_728
    everything = decoder.remat_plan(dec, layers, x, vocab, 1 << 40,
                                    state_bytes)
    assert everything.layers_extended == 10
    assert everything.kept_extra_bytes == (
        9 * (278_921_216 + 134_217_728) + 10 * 2 * 268_435_456)


@pytest.mark.parametrize("capacity, state", [
    (None, 7_901_399_048), (0, 7_901_399_048), (V5E_BYTES, None)])
def test_no_capacity_or_no_state_is_the_base_set(nemotron, capacity, state):
    dec, layers, x, vocab, _ = nemotron
    plan = decoder.remat_plan(dec, layers, x, vocab, capacity, state)
    assert plan.extras == ((),) * 9
    assert (plan.kept_extra_bytes, plan.layers_extended, plan.bytes_left) == (
        0, 0, 0)
    assert plan.base_bytes == 3_009_413_120       # counted all the same


@pytest.mark.parametrize("cell, eighths, extended", [
    ("nemotron", range(96, 128), 8),              # 12 to 15.875 GiB
    ("keye", range(80, 128), 6)])                 # 10 to 15.875: by kind too
def test_more_capacity_never_keeps_less(request, cell, eighths, extended):
    dec, layers, x, vocab, state_bytes = request.getfixturevalue(cell)
    before, steps = None, 0
    for eighth in eighths:
        plan = decoder.remat_plan(dec, layers, x, vocab, eighth << 27,
                                  state_bytes)
        if before is not None:
            assert before.kept_extra_bytes <= plan.kept_extra_bytes, eighth
            steps += before.kept_extra_bytes < plan.kept_extra_bytes
        before = plan
    assert steps >= 4 and before.layers_extended == extended


def test_a_batch_over_several_chips_is_planned_at_a_chips_share(
        nemotron, monkeypatch):
    """Under `kernel_sharding` over four chips of the batch axis the step
    asks the plan at a chip's share of the batch, with the state, the
    loss's chunk and every weight-sized term whole: nothing of the account
    is divided by the chips."""
    from jax.sharding import PartitionSpec

    dec, layers, x, vocab, state_bytes = nemotron
    asked = []
    monkeypatch.setattr(decoder, "remat_plan", lambda *a: asked.append(a)
                        or decoder.RematPlan((), 0, 0, 0, 0, 0, None, 0))
    mesh = jax.make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    x8 = jax.ShapeDtypeStruct((8,) + x.shape[1:], x.dtype)
    with attention.kernel_sharding(mesh, PartitionSpec("dp", None, None,
                                                      None)), \
            attention.step_memory(state_bytes=state_bytes, capacity=1 << 40):
        decoder._planned_extras(dec, layers, x8, vocab)
    (_, _, a_chips, rows, capacity, state, chips, losses), = asked
    assert a_chips.shape == (2,) + x.shape[1:]
    assert (rows, capacity, state, chips) == (vocab, 1 << 40, state_bytes, 4)
    assert losses == 1      # a stack with no prediction module behind it


def test_only_activations_shrink_with_a_chips_share(nemotron):
    """Half the batch on a chip: what the blocks keep halves, and the
    reserve does not fall below what the batch does not split, the loss's
    chunk and head gradient (ops.loss.working_set_bytes) and the experts'
    weight-sized gradients (parallel.moe.held_backward_bytes)."""
    from ray_tpu.ops.loss import working_set_bytes

    dec, layers, x, vocab, state_bytes = nemotron
    two = jax.ShapeDtypeStruct((2,) + x.shape[1:], x.dtype)
    whole = decoder.remat_plan(dec, layers, two, vocab, 1 << 40, state_bytes)
    half = decoder.remat_plan(dec, layers, x, vocab, 1 << 40, state_bytes)
    assert half.extras == whole.extras
    # (all but four layers' rows held an expert, 16 integers, whatever the
    # batch)
    assert half.kept_extra_bytes * 2 == whole.kept_extra_bytes + 4 * 16 * 4
    assert half.base_bytes * 2 == whole.base_bytes
    assert half.state_bytes == whole.state_bytes
    assert half.reserve_bytes * 2 > whole.reserve_bytes
    experts = sum(a.size * a.dtype.itemsize for a in (
        layers[1]["expert_up"], layers[1]["expert_down"]))
    assert half.reserve_bytes > experts
    assert half.reserve_bytes >= working_set_bytes(
        16384, x.shape[-1], vocab) == 6 * 4096 * vocab + 4 * 2688 * vocab


_MAMBA, _EXPERTS = ("ssm_gated", "ssm_in_proj"), ("moe_choice", "moe_shared_up")


def _offered(monkeypatch) -> collections.Counter:
    """name -> bytes of every candidate the blocks' accounts list from here
    on (a block is traced once a kind and shape), a layer's values of one
    name together."""
    offered = collections.Counter()
    real = decoder._block_account

    def account(*args, **kwargs):
        out = real(*args, **kwargs)
        for name, size, _ in out[3]:
            offered[name] += size
        return out

    monkeypatch.setattr(decoder, "_block_account", account)
    return offered


@pytest.mark.parametrize("family, config, traffic, extras, kept", [
    ("olmoe", "olmoe-1b-7b", "pretrain-olmoe-b4-s4096", ((),) * 2, 0),
    ("granite_hybrid", "granite-4.0-h-micro", "pretrain-granite4h-b1-s16384",
     (("mlp_gate_up", "ssm_gated", "ssm_in_proj"), ("mlp_gate_up",))
     + ((),) * 8, 1_486_880_768),
    ("sambay", "phi-4-mini-flash-reasoning", "pretrain-phi4flash-b1-s16384",
     (("mlp_gate_up", "ssm_in_proj"), ("mlp_gate_up",),
      ("mlp_gate_up", "ssm_in_proj")) + ((),) * 5, 2_684_354_560),
    ("olmo_hybrid", "olmo-hybrid-7b", "pretrain-olmohybrid-b1-s16384",
     (("gated_delta_in", "mlp_gate_up"), ("gated_delta_in",)) + ((),) * 2,
     1_853_882_368),
    ("nemotron_h", "nemotron-3-nano-30b-a3b",
     "pretrain-nemotron3nano-b1-s16384",
     (_MAMBA, _EXPERTS, _MAMBA, _EXPERTS, _MAMBA, (), _EXPERTS, _MAMBA,
      _EXPERTS), 2_865_234_176),
    ("lfm2_moe", "lfm2-8b-a1b", "pretrain-lfm2moe-s8192",
     (("mlp_gate_up",),) + (("moe_choice",),) * 4, 945_815_808),
], ids=["olmoe", "granite4h", "phi4flash", "olmohybrid", "nemotron3nano",
        "lfm2moe"])
def test_a_block_joined_by_the_add_offers_no_branch_output(
        monkeypatch, family, config, traffic, extras, kept):
    """The six rematerialised cells whose layers hold no hyper-connection:
    no block's account lists `hc_channel_out` (the name is given where
    `write` is the hyper-connection's, and nowhere else), none has a latent
    layer, so none offers q either, and the plan at a v5e's capacity is
    pinned name for name and byte for byte: PR 55's in OLMoE's and
    Nemotron's cells; in the other four what the same rule gives since PR
    58, with each attention layer's lse at 4 bytes a row where it was
    padded to 128 lanes (0.25 to 1.33 GB less in the base set: granite's
    second layer keeps its gate and up where it kept its input
    projection, phi4flash's first three layers their gate and up and both
    Mamba-1 layers their input projection, olmohybrid's second layer its
    q | k | v and gate, LFM2's dense layer its gate and up)."""
    import importlib

    dec, layers, x, vocab, state_bytes = _cell(
        monkeypatch, importlib.import_module(f"chipbench.families.{family}"),
        config, traffic)
    assert dec.hyper is None
    assert decoder.LATENT_ATTENTION not in dec.kinds
    offered = _offered(monkeypatch)
    plan = decoder.remat_plan(dec, layers, x, vocab, V5E_BYTES, state_bytes)
    assert not set(offered) & {"hc_channel_out", "flash_attention_q"}
    # nor what a sparse block's q, k and v are made from, though granite's,
    # olmo-hybrid's, Nemotron's and LFM2's attention layers run the arm of
    # `_qkv_heads` that names them: candidates of that kind alone
    assert not set(offered) & set(
        decoder.FITS_BY_KIND[decoder.SPARSE_ATTENTION])
    assert plan.extras == extras
    assert plan.kept_extra_bytes == kept


def test_a_sparse_block_offers_what_its_q_k_v_are_made_from(keye,
                                                            monkeypatch):
    """Keye-VL-2.0's six sparse blocks at 16,384 tokens of 32 | 4 heads of
    128, `wq` and `wkv` apart: each offers q as the kernels take it and,
    by its kind's own table, the q projection's output ([16384, 4096]
    bfloat16, as large), the k | v projection's ([16384, 1024]), k normed
    and rotated at kv-head width ([1, 4, 16384, 128]) and the branch's
    output ([16384, 2048]), beside its routing's choices; never k's and
    v's copies across a group. Beside 5.29 GB of state on a v5e all six
    keep all six, and 1.7 GB are left."""
    dec, layers, x, vocab, state_bytes = keye
    assert set(dec.kinds) == {decoder.SPARSE_ATTENTION}
    offered = _offered(monkeypatch)
    plan = decoder.remat_plan(dec, layers, x, vocab, V5E_BYTES, state_bytes)
    sizes = {"attention_q_proj": 134_217_728, "attention_kv_proj": 33_554_432,
             "attention_k_heads": 16_777_216,
             "sparse_attention_out": 67_108_864,
             "flash_attention_q": 134_217_728, "moe_choice": 1_572_928}
    assert offered == sizes
    assert plan.extras == (tuple(sorted(sizes)),) * 6
    assert plan.kept_extra_bytes == 6 * sum(sizes.values()) == 2_324_693_376
    assert plan.bytes_left == 1_666_242_872
    # the order is cost saved a byte kept: with room for 1.4 GB, all six q,
    # rotated k and choices (what stands first), then all six outputs (two
    # matmuls an element), then the k | v projections of two layers (one)
    some = decoder.remat_plan(
        dec, layers, x, vocab, V5E_BYTES - plan.bytes_left
        - plan.kept_extra_bytes + 14 * 10 ** 8, state_bytes)
    firsts = ("attention_k_heads", "flash_attention_q", "moe_choice",
              "sparse_attention_out")
    assert some.extras == (
        tuple(sorted(firsts + ("attention_kv_proj",))),) * 2 + (firsts,) * 4


@pytest.fixture
def ling(monkeypatch):
    from chipbench.families import bailing_hybrid
    return _cell(monkeypatch, bailing_hybrid, "ling-3.0-flash",
                 "pretrain-ling3flash-b1-s16384")


def test_a_kda_block_keeps_its_rules_three_and_counts_the_log_decay(
        ling, monkeypatch):
    """Ling-3.0-flash's period at 16,384 tokens of 32 heads of 128 | 128: a
    KDA block keeps, beside its input, the rule's output [16384, 4096]
    (134 MB), the state entering each of 256 chunks in the model's dtype
    (268 MB) and T - I (67 MB), by its kind's base set; it offers its q | k
    | v projection [16384, 12288] bfloat16 and the float32 log-decay
    [16384, 4096], 268 MB where `gated_delta` has a [T, 30]; and its
    reserve counts eight float32 values of that size under no name
    (`_kda_holds`)."""
    dec, layers, x, vocab, state_bytes = ling
    assert dec.kinds == (decoder.KDA,) * 5 + (decoder.LATENT_ATTENTION,)
    assert 7.60e9 < state_bytes < 7.70e9
    assert decoder._kept(decoder.KDA) == decoder.KEPT_UNDER_REMAT + (
        "kda_o", "kda_states", "kda_T")
    offered = _offered(monkeypatch)
    plan = decoder.remat_plan(dec, layers, x, vocab, V5E_BYTES, state_bytes)
    log_decay = 16384 * 4096 * 4
    # one account a kind and channel mixer: the dense KDA block's, an expert
    # KDA block's, the latent block's
    assert offered["kda_in"] == 2 * 16384 * 12288 * 2
    assert offered["kda_g"] == 2 * log_decay == 536_870_912
    assert decoder._kda_holds(decoder.KDA, 16384, layers[0]) == 8 * log_decay
    assert decoder._kda_holds(decoder.LATENT_ATTENTION, 16384, layers[5]) == 0
    kept = 16384 * 4096 * 2 + 256 * 32 * 128 * 128 * 2 + 256 * 32 * 64 * 64 * 2
    assert plan.base_bytes > 5 * (kept + 16384 * 2560 * 2)
    assert plan.reserve_bytes > 8 * log_decay + kept
    # what is left beside 7.66 GB of state goes to the first layer's
    # projection, the choices and the shared experts' up projections; no
    # layer has room for its float32 log-decay
    assert plan.extras[0] == ("kda_in",)
    assert all("kda_g" not in names for names in plan.extras)
    assert all({"moe_choice", "moe_shared_up"} <= set(names)
               for names in plan.extras[1:])
    assert "flash_attention_q" in plan.extras[5]
    assert plan.state_bytes + plan.base_bytes + plan.reserve_bytes \
        + plan.kept_extra_bytes <= V5E_BYTES - 2 ** 30
    # with nothing known of the chip nothing is added
    assert decoder.remat_plan(dec, layers, x, vocab, None,
                              state_bytes).extras == ((),) * 6


def test_the_second_table_is_beside_the_first():
    # one name is of both: q, a candidate of the two kinds whose base set
    # leaves it out (a latent and a sparse block's, KEPT_BY_KIND) and of no
    # other's
    assert set(decoder.KEPT_WHERE_IT_FITS) & set(decoder.KEPT_UNDER_REMAT) \
        == {"flash_attention_q"}
    assert [kind for kind in decoder.MIXERS
            if "flash_attention_q" not in decoder._kept(kind)] \
        == [decoder.LATENT_ATTENTION, decoder.SPARSE_ATTENTION,
            decoder.WINDOWED_ATTENTION, decoder.ATTENTION_NOPE]
    # and a third beside both, by kind: a sparse block's, a KDA block's and
    # a gated grouped-query block's own candidates, names of neither table
    assert list(decoder.FITS_BY_KIND) == [
        decoder.SPARSE_ATTENTION, decoder.KDA, decoder.WINDOWED_ATTENTION,
        decoder.ATTENTION_NOPE]
    assert decoder.FITS_BY_KIND[decoder.WINDOWED_ATTENTION] \
        is decoder.FITS_BY_KIND[decoder.ATTENTION_NOPE]
    assert set(decoder.FITS_BY_KIND[decoder.KDA]) == {"kda_in", "kda_g"}
    for own in decoder.FITS_BY_KIND.values():
        assert not set(own) & (set(decoder.KEPT_WHERE_IT_FITS)
                               | set(decoder.KEPT_UNDER_REMAT))
    own = set(decoder.FITS_BY_KIND[decoder.SPARSE_ATTENTION])
    assert set(decoder._fits(decoder.SPARSE_ATTENTION)) \
        == own | set(decoder.KEPT_WHERE_IT_FITS)
    assert decoder._fits(decoder.ATTENTION) == decoder.KEPT_WHERE_IT_FITS
    value = jax.ShapeDtypeStruct((16384, 10304), jnp.bfloat16)
    per_byte = {name: cost(value, 2688) / (value.size * 2)
                for name, cost in decoder.KEPT_WHERE_IT_FITS.items()}
    assert per_byte["ssm_in_proj"] == 2688.0      # 2 d flops for 2 bytes
    assert per_byte["moe_choice"] > per_byte["ssm_in_proj"] > per_byte[
        "ssm_gated"] > 0
    # what closes a hyper-connected block's channel branch: four matmuls
    # an element (as the chip ordered them: PERF.md section 6, PR 56)
    assert per_byte["moe_choice"] > per_byte["hc_channel_out"] == 4 * 2688.0


# ---------------------------------------------------------------------------
# an extended policy changes no number
# ---------------------------------------------------------------------------
@pytest.fixture
def tiny():
    cfg = dataclasses.replace(NemotronHConfig.tiny(), dtype=jnp.float32)
    params = nemotron_h_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    return cfg, params, (tok, jnp.roll(tok, -1, axis=1))


def test_loss_and_every_gradient_under_an_extended_policy(tiny, monkeypatch):
    """MEM*E with the kernels interpreted: inside `step_memory` with room
    for everything every layer but the attention's keeps its candidates
    (the plan is asked once, and the blocks' policies hold the names); the
    loss and every gradient are the base policy's."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg, params, batch = tiny
    asked = []
    real = decoder.remat_plan

    def spy(*args, **kwargs):
        asked.append(real(*args, **kwargs))
        return asked[-1]

    monkeypatch.setattr(decoder, "remat_plan", spy)
    step = jax.value_and_grad(lambda p: nemotron_h_loss(p, batch, cfg))
    base_loss, base = step(params)
    assert not asked                      # no step's memory: no plan at all
    with attention.step_memory(state_bytes=0, capacity=1 << 40):
        jaxpr = jax.make_jaxpr(step)(params)
        loss, grads = step(params)
    mamba, experts = ("ssm_gated", "ssm_in_proj"), ("moe_choice",
                                                   "moe_shared_up")
    assert [plan.extras for plan in asked] == [
        (mamba, experts, mamba, (), experts)] * 2
    policies = {str(eqn.params["policy"]) for eqn in jaxpr.jaxpr.eqns
                if "policy" in eqn.params}
    assert len(policies) == 3, policies   # Mamba's, the experts', the base
    np.testing.assert_allclose(loss, base_loss, rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(base)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_the_train_step_hands_its_state_down(tiny, monkeypatch):
    """`make_train_step_for`'s step counts what it holds, parameters,
    optimizer state and gradients the size of the parameters, and traces
    its loss inside `step_memory`; on the CPU no device names a capacity,
    so the plan is not asked and the step is the base set's."""
    from ray_tpu.models._training import step_state_bytes
    from ray_tpu.models.nemotron_h import make_nemotron_h_train_step

    cfg, _, batch = tiny
    seen = []
    real = decoder._chip_capacity
    monkeypatch.setattr(decoder, "_chip_capacity", lambda mesh: seen.append(
        attention.step_memory_given()) or real(mesh))
    monkeypatch.setattr(decoder, "remat_plan", lambda *a, **k: 1 / 0)
    init_state, train_step = make_nemotron_h_train_step(cfg)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
    train_step.trace(state, batch)
    params = sum(a.size * a.dtype.itemsize
                 for a in jax.tree.leaves(state["params"]))
    assert step_state_bytes(state) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(state)) + params
    assert seen == [(step_state_bytes(state), None)]
    assert real(None) is None
