"""ops/loss.py cross_entropy: the one loss of every decoder family.

Value and gradients against a plain float32 log_softmax loss, with no
mesh and under sharded train steps; row counts the chunk does not divide;
a tied table's gradient as one sum over chips (chip_views) against the
plain path's two; and, in the compiled program of a data-parallel step on the CPU mesh,
what says that each chip scans its own rows and keeps no logits."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import GPTConfig, gpt_forward, gpt_init, gpt_loss
from ray_tpu.models.gpt import make_train_step, shard_batch, shard_params
from ray_tpu.models import decoder
from ray_tpu.ops import loss as loss_ops
from ray_tpu.ops.attention import kernel_sharding
from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules
from ray_tpu.util import profiling

MESHES = {"no_mesh": None, "dp4": MeshConfig(dp=4),
          "dp4_tp2": MeshConfig(dp=4, tp=2)}


def plain_loss(params, batch, cfg):
    tokens, targets = batch
    logp = jax.nn.log_softmax(
        gpt_forward(params, tokens, cfg).astype(jnp.float32), axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _batch(cfg, b=8, s=32):
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b, s)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, 1))


def _sharded_grads(cfg, mesh_name, params, batch):
    """(loss, gradients, the compiled text) of gpt_loss under the mesh's
    sharded step."""
    n = MESHES[mesh_name].dp * MESHES[mesh_name].tp
    mesh = make_mesh(MESHES[mesh_name], devices=jax.devices()[:n])
    rules = tp_rules()

    def step(p, b):
        with kernel_sharding(mesh, rules.spec(("batch", "heads", None, None))):
            return jax.value_and_grad(lambda p, b: gpt_loss(p, b, cfg))(p, b)

    args = shard_params(params, cfg, mesh, rules), shard_batch(batch, mesh)
    compiled = jax.jit(step).lower(*args).compile()
    return (*compiled(*args), compiled.as_text())


def _table_reduces(text, cfg):
    """The all-reduces of a compiled program that take in a whole
    [vocab, d_model] array (or, under tp, its vocabulary shard)."""
    shapes = {f"[{v},{cfg.d_model}]" for v in (cfg.vocab_size,
                                               cfg.vocab_size // 2)}
    return sum(any(shape.endswith(s) for s in shapes)
               for c in profiling.collective_calls(text)["collectives"]
               if c["kind"] == "all-reduce" for shape in c["operands"])


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_loss_and_gradients_equal_the_plain_float32_loss(mesh_name, dtype,
                                                         tol, monkeypatch):
    # 64 rows a chip under dp=4: two chunks each, four with no mesh.
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 32)
    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=dtype)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    as_f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    want, want_grads = jax.value_and_grad(plain_loss)(
        as_f32, batch, dataclasses.replace(cfg, dtype=jnp.float32))

    if MESHES[mesh_name] is None:
        got, grads = jax.jit(jax.value_and_grad(
            lambda p, b: gpt_loss(p, b, cfg)))(params, batch)
    else:
        got, grads, _ = _sharded_grads(cfg, mesh_name, params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    # "embed" is the tied head: the loss's own dhead and, through dx,
    # every other parameter.
    for (path, w), g in zip(jax.tree.leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), w, atol=tol * np.abs(w).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("rows", [(3, 5), (2, 3), (4, 6), (7,)],
                         ids=["15_unchunked", "6_by_2", "24_by_4",
                              "7_unchunked"])
def test_a_row_count_the_chunk_does_not_divide(rows, monkeypatch):
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 4)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, rows + (16,), jnp.float32)
    head = jax.random.normal(k2, (16, 40), jnp.float32)
    targets = jax.random.randint(k3, rows, 0, 40)

    def plain(x, head):
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1))

    want = jax.value_and_grad(plain, argnums=(0, 1))(x, head)
    got = jax.value_and_grad(
        lambda x, head: loss_ops.cross_entropy(x, head, targets),
        argnums=(0, 1))(x, head)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def _computations(hlo: str) -> dict:
    """{computation name: its text} of a compiled module's text."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.-]+) \([^\n]*\{\n.*?^\}", hlo, re.M | re.S)}


def test_dp_step_scans_each_chips_own_rows_and_keeps_no_logits(monkeypatch):
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 16)
    cfg = GPTConfig.tiny()
    mesh = make_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
    init_state, step = make_train_step(cfg, mesh=mesh, rules=tp_rules())
    state = init_state(jax.random.PRNGKey(0))
    batch = shard_batch(_batch(cfg), mesh)        # 256 rows, 64 a chip
    hlo = step.lower(state, batch).compile().as_text()
    comps = _computations(hlo)
    entry = next(t for t in comps.values() if t.startswith("ENTRY"))
    loops = [t for t in comps.values() if t is not entry]
    v, d = cfg.vocab_size, cfg.d_model
    # The scan is there, over this chip's 4 chunks of 16 rows, not the
    # batch's 16 ...
    assert f"[4,16,{d}]" in hlo and f"[16,16,{d}]" not in hlo
    # ... no chip is handed the other chips' rows ...
    assert "all-gather" not in hlo
    # ... the head's gradient is reduced once, outside every loop (every
    # all-reduce is the entry computation's own) ...
    assert not any(" all-reduce" in t for t in loops)
    reduced = " ".join(line.split(" all-reduce(")[0]
                       for line in entry.splitlines()
                       if " all-reduce(" in line)
    assert f"[{d},{v}]" in reduced or f"[{v},{d}]" in reduced
    # ... and no [chunks, chunk, vocab] stack of logits is kept.
    assert not re.search(r"\[\d+,16,%d\]" % v, hlo)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # The state comes back placed as init_state placed it: the second call
    # runs the first call's executable, the step is compiled once.
    step(state, batch)
    assert step._cache_size() == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mesh_name", ["dp4", "dp4_tp2"])
def test_a_tied_table_crosses_the_chips_once(mesh_name, dtype, monkeypatch):
    """The lookup's and the head's gradients of a tied table are added on
    their chip and reduced as one sum: the value is the plain path's (two
    reduces, then the add) to one step of the dtype, with one reduce of
    the table in the program."""
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 32)
    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=dtype)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    loss, grads, text = _sharded_grads(cfg, mesh_name, params, batch)
    monkeypatch.setattr(decoder, "chip_views", lambda table: None)
    plain_loss_, plain, plain_text = _sharded_grads(cfg, mesh_name, params,
                                                    batch)
    # (XLA:TPU leaves the plain path its two, tests/test_compile_v5e_loss.py;
    # the CPU's compiler can merge them itself.)
    assert _table_reduces(text, cfg) == 1
    assert _table_reduces(plain_text, cfg) in (1, 2)
    assert float(loss) == float(plain_loss_)
    step = float(jnp.finfo(dtype).eps)
    for (path, want), got in zip(jax.tree.leaves_with_path(plain),
                                 jax.tree.leaves(grads)):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=step,
            atol=step * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("mesh_name", ["dp4", "dp4_tp2"])
def test_an_untied_head_takes_the_plain_path(mesh_name, monkeypatch):
    """Two tables are two parameters: nothing to add before the reduce,
    no views made, gradients the float32 reference's."""
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 32)
    monkeypatch.setattr(decoder, "chip_views", None)    # a call would raise
    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32,
                              tie_embeddings=False)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    want, want_grads = jax.value_and_grad(plain_loss)(params, batch, cfg)
    got, grads, _ = _sharded_grads(cfg, mesh_name, params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    assert {"embed", "head"} <= set(grads)
    for (path, w), g in zip(jax.tree.leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g), w, atol=2e-5 * np.abs(w).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("rows", [(8, 3), (4, 7), (8, 5, 2)],
                         ids=["6_a_chip_by_2", "7_a_chip_unchunked",
                              "20_a_chip_by_4"])
def test_views_of_the_head_with_rows_the_chunk_does_not_divide(rows,
                                                                monkeypatch):
    """cross_entropy over a head given as chip_views, and lookup from the
    same views: value and both gradients equal the plain formulation's."""
    monkeypatch.setattr(loss_ops, "_LOSS_CHUNK", 4)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    table = jax.random.normal(k1, (40, 16), jnp.float32)
    w = jax.random.normal(k2, (16, 16), jnp.float32) / 4
    tokens = jax.random.randint(k3, rows, 0, 40)
    targets = jax.random.randint(k4, rows, 0, 40)

    def plain(table, w):
        logp = jax.nn.log_softmax((table[tokens] @ w) @ table.T, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], axis=-1))

    mesh = make_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])

    def tied(table, w, tokens, targets):
        with kernel_sharding(
                mesh, tp_rules().spec(("batch", "heads", None, None))):
            views = loss_ops.chip_views(table)
            assert views.shape == (4, 40, 16)
            x = loss_ops.lookup(views, tokens) @ w
            return loss_ops.cross_entropy(x, views.swapaxes(1, 2), targets)

    want = jax.value_and_grad(plain, argnums=(0, 1))(table, w)
    got = jax.jit(jax.value_and_grad(tied, argnums=(0, 1)))(
        table, w, *shard_batch((tokens, targets), mesh))
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-5, atol=1e-6)


def test_no_views_where_the_batch_axes_span_one_chip():
    """A tp-only mesh and no mesh at all: no batch axis of more than one
    chip, so no gradient crosses chips twice and the table stays whole."""
    table = jnp.ones((8, 4))
    assert loss_ops.chip_views(table) is None
    mesh = make_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    with kernel_sharding(mesh,
                         tp_rules().spec(("batch", "heads", None, None))):
        assert loss_ops.chip_views(table) is None
