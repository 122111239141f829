"""models.sambay (Phi-4-mini-flash-reasoning's family) against the plain
float32 reference, chipbench/families/sambay.py: the recurrence token by
token, both softmax maps of a pair as dense masked matrices, the layer
kinds from the depth by the model's own rule, nothing shared with ray_tpu.
CPU, `SambaYConfig.tiny()`, float32 at highest matmul precision, seeded
random weights with every parameter perturbed (the initialisation's zero
biases and unit norms would hide a bias that is dropped); the kernels run
interpreted (RAY_TPU_PALLAS_INTERPRET=1) beside their jax.numpy form.
Tolerance 2e-4 of the largest value: the two differ by the order of their
float32 sums alone (readings: logits 2e-5 of 5, gradients 4e-4 relative at
N = 12 at worst, in the lambda vectors, whose gradients are 1e-3 small)."""

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import sambay as reference
from ray_tpu.models import decoder
from ray_tpu.models.generate import cached_forward, init_cache
from ray_tpu.models.sambay import (CROSS, FULL, GMU, MAMBA, WINDOWED,
                                   SambaYConfig, make_sambay_train_step,
                                   sambay_forward, sambay_init, sambay_loss,
                                   sambay_param_axes)

TOL = 2e-4


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


def _setup(n_layers, seq, form):
    """(cfg, perturbed float32 params, (tokens, targets)); the interpreted
    kernels need 128 positions, the jax form takes any."""
    cfg = dataclasses.replace(SambaYConfig.tiny(n_layers), dtype=jnp.float32,
                              remat=False)
    params = sambay_init(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    seq = 128 if form == "interpreted" else seq
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0,
                                cfg.vocab_size)
    return cfg, params, (tokens, jnp.roll(tokens, -1, 1))


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = max(1e-3, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        float(np.max(np.abs(got - want))), scale)


def test_layer_kinds_and_parameter_count_of_the_published_model():
    cfg = SambaYConfig.phi4_mini_flash()
    kinds = cfg.layer_kinds
    assert Counter(kinds) == {MAMBA: 9, WINDOWED: 8, FULL: 1, GMU: 7,
                              CROSS: 7}
    assert kinds[16] == MAMBA and kinds[17] == FULL and kinds[18] == GMU
    assert [k for k in kinds[:16:2]] == [MAMBA] * 8
    assert [k for k in kinds[1:16:2]] == [WINDOWED] * 8
    # the reference says the same from the depth alone
    names = {MAMBA: reference.MAMBA, WINDOWED: reference.WINDOWED,
             FULL: reference.FULL, GMU: reference.GMU, CROSS: reference.CROSS}
    for n in (8, 12, 32):
        ours = SambaYConfig.tiny(n).layer_kinds
        assert tuple(names[k] for k in ours) == reference.layer_kinds(n)
    assert SambaYConfig.tiny(8).layer_kinds == (
        MAMBA, WINDOWED, MAMBA, WINDOWED, MAMBA, FULL, GMU, CROSS)
    shapes = jax.eval_shape(lambda: sambay_init(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert round(count / 1e9, 2) == 3.85
    assert (cfg.mamba_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    # the axes tree has the parameters' structure
    axes = sambay_param_axes(cfg)
    jax.tree.structure(shapes).flatten_up_to(axes)


@pytest.mark.parametrize("n_layers", [8, 12])
def test_logits_are_the_references(form, n_layers):
    cfg, params, (tokens, _) = _setup(n_layers, 40, form)
    _close(sambay_forward(params, tokens, cfg),
           reference.reference_logits(params, tokens, cfg))


@pytest.mark.parametrize("n_layers", [8, 12])
def test_loss_and_every_gradient_are_the_references(form, n_layers):
    """N = 12 has two GMU and two cross layers: the shared values'
    gradients are summed from several readers."""
    cfg, params, batch = _setup(n_layers, 40, form)
    got, got_grads = jax.value_and_grad(
        lambda p: sambay_loss(p, batch, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.reference_loss(p, *batch, cfg))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(got_grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads), strict=True):
        assert float(jnp.max(jnp.abs(w))) > 0, path   # every weight counts
        _close(g, w, 1e-3)


def test_remat_changes_nothing(form):
    cfg, params, batch = _setup(8, 40, form)
    kept = dataclasses.replace(cfg, remat=True)
    a, ga = jax.value_and_grad(lambda p: sambay_loss(p, batch, cfg))(params)
    b, gb = jax.value_and_grad(lambda p: sambay_loss(p, batch, kept))(params)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        _close(x, y, 1e-5)


@pytest.mark.parametrize("n_layers", [8, 12])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        form, n_layers):
    """Logits, not tokens: a prefill of all but the last eight positions,
    then one token at a time; the Mamba-1 layers from their cached state,
    the windowed layers under the window's mask, the GMU layers from the
    tokens in flight and the cross layers from layer N/2 + 1's cache."""
    cfg, params, (tokens, _) = _setup(n_layers, 40, form)
    seq = tokens.shape[1]
    want = reference.reference_logits(params, tokens, cfg)
    cache = init_cache(cfg, 2, seq + 8)
    kinds = cfg.layer_kinds
    assert [sorted(c) for c in cache] == [
        ["conv", "ssm"] if k == MAMBA else [] if k in (GMU, CROSS)
        else ["k", "v"] for k in kinds]
    got, cache = cached_forward(params, tokens[:, :seq - 8], cache, 0, cfg)
    parts = [got]
    for i in range(seq - 8, seq):
        got, cache = cached_forward(params, tokens[:, i:i + 1], cache, i,
                                    cfg)
        parts.append(got)
    _close(jnp.concatenate(parts, axis=1), want)
    states = reference.reference_final_states(params, tokens, cfg)
    mamba = [c["ssm"] for c, k in zip(cache, kinds) if k == MAMBA]
    for got_state, want_state in zip(mamba, states, strict=True):
        _close(got_state, want_state)


def test_a_window_longer_than_the_sequence_is_no_window(form):
    cfg, params, (tokens, _) = _setup(8, 40, form)
    wide = dataclasses.replace(cfg, sliding_window=4096)
    _close(sambay_forward(params, tokens, wide),
           reference.reference_logits(params, tokens, wide))
    assert float(jnp.max(jnp.abs(
        sambay_forward(params, tokens, wide)
        - sambay_forward(params, tokens, cfg)))) > 1e-3


def test_each_planted_fault_moves_the_logits(form):
    """The faults chipbench plants to set the cell's limits do change the
    program (a fault that changed nothing would prove no limit)."""
    cfg, params, (tokens, _) = _setup(8, 40, form)
    if form == "jax":      # chunk_carry_dropped needs whole chunks
        tokens = jnp.tile(tokens, (1, 4))[:, :128]
    clean = sambay_forward(params, tokens, cfg)
    for fault in reference.STRUCTURAL_FAULTS:
        with reference.planted(fault):
            moved = sambay_forward(params, tokens, cfg)
        assert float(jnp.max(jnp.abs(moved - clean))) > 1e-3, fault
    _close(sambay_forward(params, tokens, cfg), clean, 1e-6)


def test_train_step_lowers_the_loss():
    cfg = SambaYConfig.tiny(8)
    init_state, step = make_sambay_train_step(cfg, donate=False)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    batch = (tokens, jnp.roll(tokens, -1, 1))
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_layer_norm_is_the_definition():
    from ray_tpu.ops.layers import layer_norm
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64)) * 3 + 1
    w = jax.random.normal(jax.random.PRNGKey(1), (64,))
    b = jax.random.normal(jax.random.PRNGKey(2), (64,))
    want = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * w + b
    _close(layer_norm(x, w, b, 1e-5), want, 1e-5)
    assert layer_norm(x.astype(jnp.bfloat16), w, b).dtype == jnp.bfloat16


def test_other_families_carry_nothing_forward():
    """A stack with no Mamba-1 and no differential layer hands `Shared()`
    through untouched and windows nothing."""
    from ray_tpu.models.hybrid import HybridConfig
    kinds = HybridConfig.tiny().decoder().kinds
    rows = [decoder.MIXERS[kind] for kind in kinds]
    assert decoder.MAMBA1 not in kinds
    assert not any(row.windowed or row.hands_on_kv for row in rows)
    assert decoder.Shared() == (None, None, None)


def test_a_mamba1_block_keeps_the_scan_kernels_outputs_and_nothing_else(
        monkeypatch, capsys):
    """Under the family's remat policy a Mamba-1 block's backward pass is
    handed its arguments and the two values the scan kernel made
    (`selective_scan_m`, `selective_scan_states`): the projections, the
    convolution and the gate are made again, and the forward kernel is
    not run twice."""
    import functools

    from jax.ad_checkpoint import print_saved_residuals

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(SambaYConfig.tiny(8), dtype=jnp.float32)
    dec = cfg.decoder()
    layer = sambay_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    block = jax.checkpoint(
        functools.partial(decoder._block, dec=dec, kind=dec.kinds[0],
                          mlp=dec.mlp[0]),
        policy=dec.remat)
    b, s = 2, 128
    print_saved_residuals(lambda x, layer: block(x, layer, None, None)[0],
                          jnp.ones((b, s, cfg.d_model)), layer)
    lines = capsys.readouterr().out.splitlines()
    kept = sorted(line.split()[0] for line in lines
                  if "from the argument" not in line
                  and "from a constant" not in line)
    inner, N = cfg.mamba_inner, cfg.mamba_d_state
    assert kept == sorted([f"f32[{b},{s},{inner}]",
                           f"f32[{b},{s // 64},{N},1,128]"]), lines
    # m is kept through the copy jax.checkpoint puts after a named value
    # that the forward reads too (the gate): still the kernel's m
    made = [line for line in lines if "from the argument" not in line]
    assert len(made) == 2 and all("(selective_scan)" in n for n in made)
    assert any("named 'selective_scan_states'" in n for n in made)
