"""Compile-only, against a described v5e:2x2 (no chip, no timings): a
rematerialised step whose batch is split over four chips keeps what
`models.decoder.remat_plan` says fits A CHIP, and XLA's figure for a chip
stays a GiB under its 15.75. No cell runs remat on more than one chip, so
this is the only place the plan's account of such a step meets XLA's.

The model is a SwiGLU decoder at widths of the 1B class with granite's
vocabulary (100,352 rows) and `decoder.keep_kernel_outputs`, 16 sequences
of 4,096 tokens over dp=4: 16,384 tokens a chip. What the batch does not
split is whole on every chip (the loss's chunk and head gradient, 3.29 GB:
ops.loss.working_set_bytes), and under data parallelism XLA moves every
layer's weight gradients behind the last backward kernel
(models/_training.py `_ASYNC_GRADIENT_REDUCE`), so all four blocks'
working sets count at once: the plan kept two layers' gate | up where a
one-chip step of the same share would keep all four, while a layer's lse
was padded to 128 lanes (0.13 GB a layer, in the base set and again in each
block's working set); at 4 bytes a row, since PR 58, all four fit, with
0.26 GB left. (At eight layers the
same step stands at 15.26 GiB with the base set alone, 4.2 GB above the
one-chip step of a chip's share, and the plan adds nothing: PERF.md
section 6, PR 51.)"""

import pytest

from compile_v5e import HBM_BYTES, topo, total  # noqa: F401

PLANS = []                      # (x, chips, the plan) as the step asked


@pytest.fixture(scope="module")
def step(topo):
    """The compiled dp=4 step, its blocks' policies the plan's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import ray_tpu.ops.attention as attention
    from ray_tpu.models import decoder
    from ray_tpu.models.llama import LlamaConfig, make_llama_train_step
    from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules

    class KernelOutputsKept(LlamaConfig):
        def decoder(self):
            return super().decoder()._replace(
                remat=decoder.keep_kernel_outputs)

    cfg = KernelOutputsKept(
        vocab_size=100352, d_model=2048, n_heads=16, n_kv_heads=8,
        n_layers=4, d_ff=8192, max_seq_len=4096)
    mesh = make_mesh(MeshConfig(dp=4), devices=topo.devices)
    whole = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        jax.eval_shape(lambda: make_llama_train_step(cfg)[0](
            jax.random.PRNGKey(0))))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)

        def planned(dec, layers, x, *rest, _plan=decoder.remat_plan):
            # rest: vocab, capacity, state_bytes, chips, losses
            PLANS[:] = [(x, rest[3], _plan(dec, layers, x, *rest))]
            return PLANS[0][2]

        patch.setattr(decoder, "remat_plan", planned)
        _, train_step = make_llama_train_step(cfg, mesh=mesh,
                                              rules=tp_rules())
        tok = jax.ShapeDtypeStruct((16, cfg.max_seq_len), jnp.int32,
                                   sharding=rows)
        # a described chip has no `memory_stats()`: its 15.75 GiB go down
        # as the step hands its state's bytes down
        with attention.step_memory(capacity=int(HBM_BYTES)):
            return train_step.lower(state, (tok, tok)).compile()


def test_the_plan_is_asked_at_a_chips_share_with_the_loss_whole(step):
    from ray_tpu.ops.loss import working_set_bytes

    (x, chips, plan), = PLANS
    assert x.shape == (4, 4096, 2048) and chips == 4
    assert plan.state_bytes == 5_301_895_176      # the whole state, a chip's
    assert plan.base_bytes == 4 * (469_762_560 - 4 * 16 * 4096 * (512 - 4))
    # the loss's 3.29 GB and every block's backward at once
    loss = working_set_bytes(16384, 2048, 100352)
    assert loss == 6 * 4096 * 100352 + 4 * 2048 * 100352
    assert plan.reserve_bytes == loss + plan.base_bytes + 4 * 536_870_912
    assert plan.extras == (("mlp_gate_up",),) * 4
    assert plan.kept_extra_bytes == 4 * 536_870_912
    assert plan.bytes_left == 259_747_832


def test_a_chip_of_the_dp4_step_stays_a_gib_under(step, record_property):
    nbytes = total(step.memory_analysis())
    record_property("dp4_remat_bytes_per_chip", nbytes)
    print(f"dp4 rematerialised step: {nbytes / 1e9:.2f} GB a chip")
    assert nbytes <= HBM_BYTES - 2 ** 30
    # and the account is from above: what the plan reckoned the step holds
    (_, _, plan), = PLANS
    assert nbytes <= (plan.state_bytes + plan.base_bytes + plan.reserve_bytes
                     + plan.kept_extra_bytes)
