"""Model + ops tests (CPU backend; kernel-vs-reference equivalence is the
test pattern — the TPU kernel path is exercised on hardware by bench.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import (
    GPTConfig,
    gpt_forward,
    gpt_init,
    gpt_loss,
    gpt_param_axes,
    make_train_step,
)
from ray_tpu.models.gpt import shard_batch, shard_params
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.layers import rms_norm, rope, swiglu
from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules, fsdp_rules


class TestAttention:
    def test_matches_reference(self):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (2, 4, 64, 32))
        k = jax.random.normal(k2, (2, 4, 64, 32))
        v = jax.random.normal(k3, (2, 4, 64, 32))
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, True, None)),
            np.asarray(mha_reference(q, k, v, True)),
            rtol=2e-3, atol=2e-3)

    def test_causality(self):
        # Changing future tokens must not change past outputs.
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(k1, (1, 2, 16, 8))
        k = jax.random.normal(k2, (1, 2, 16, 8))
        v = jax.random.normal(k3, (1, 2, 16, 8))
        out1 = flash_attention(q, k, v, True, None)
        k_mod = k.at[:, :, 10:, :].set(99.0)
        v_mod = v.at[:, :, 10:, :].set(99.0)
        out2 = flash_attention(q, k_mod, v_mod, True, None)
        np.testing.assert_allclose(
            np.asarray(out1[:, :, :10]), np.asarray(out2[:, :, :10]),
            rtol=1e-5, atol=1e-5)

    def test_grad_matches_reference(self):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(k1, (1, 2, 32, 16))
        k = jax.random.normal(k2, (1, 2, 32, 16))
        v = jax.random.normal(k3, (1, 2, 32, 16))
        g1 = jax.grad(lambda q_: flash_attention(
            q_, k, v, True, None).sum())(q)
        g2 = jax.grad(lambda q_: mha_reference(
            q_, k, v, True).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-3, atol=2e-3)


class TestLayers:
    def test_rms_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        w = jnp.ones((16,))
        out = rms_norm(x, w)
        rms = np.sqrt(np.mean(np.square(np.asarray(out)), axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-2)

    def test_rope_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
        out = rope(x)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-4)

    def test_rope_relative(self):
        # RoPE dot products depend only on relative positions.
        x = jnp.ones((1, 1, 4, 8))
        r = rope(x)
        d01 = float(jnp.dot(r[0, 0, 0], r[0, 0, 1]))
        d12 = float(jnp.dot(r[0, 0, 1], r[0, 0, 2]))
        assert abs(d01 - d12) < 1e-4

    def test_swiglu_shapes(self):
        x = jnp.ones((2, 4, 8))
        out = swiglu(x, jnp.ones((8, 16)), jnp.ones((8, 16)),
                     jnp.ones((16, 8)))
        assert out.shape == (2, 4, 8)


class TestGPT:
    def test_forward_shapes(self):
        cfg = GPTConfig.tiny()
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        logits = gpt_forward(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_loss_decreases(self):
        cfg = GPTConfig.tiny()
        init_state, train_step = make_train_step(cfg)
        state = init_state(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                    cfg.vocab_size)
        batch = (tokens, jnp.roll(tokens, -1, axis=1))
        losses = []
        for _ in range(5):
            state, m = train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert int(state["step"]) == 5

    def test_param_axes_structure_matches(self):
        cfg = GPTConfig.tiny()
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        axes = gpt_param_axes(cfg)
        leaves, treedef = jax.tree.flatten(params)
        axes_leaves = treedef.flatten_up_to(axes)
        assert len(leaves) == len(axes_leaves)
        for p, ax in zip(leaves, axes_leaves):
            assert p.ndim == len(ax)

    def test_sharded_train_step(self):
        cfg = GPTConfig.tiny()
        mesh = make_mesh(MeshConfig(dp=4, tp=2))
        init_state, train_step = make_train_step(
            cfg, mesh=mesh, rules=tp_rules())
        state = init_state(jax.random.PRNGKey(0))
        spec = state["params"]["layers"][0]["wqkv"].sharding.spec
        assert "tp" in str(spec)
        tokens = np.random.randint(0, cfg.vocab_size, (4, 32),
                                   dtype=np.int32)
        batch = shard_batch((tokens, np.roll(tokens, -1, 1)), mesh)
        state, m = train_step(state, batch)
        assert np.isfinite(float(m["loss"]))

    def test_fsdp_sharding(self):
        cfg = GPTConfig.tiny()
        mesh = make_mesh(MeshConfig(dp=1, fsdp=8, tp=1))
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        sharded = shard_params(params, cfg, mesh, fsdp_rules())
        spec = sharded["layers"][0]["w1"].sharding.spec
        assert "fsdp" in str(spec)

    def test_sharded_matches_unsharded(self):
        cfg = GPTConfig.tiny()
        tokens = np.random.randint(0, cfg.vocab_size, (4, 32),
                                   dtype=np.int32)
        batch = (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, 1)))
        init_state, train_step = make_train_step(cfg, donate=False)
        state = init_state(jax.random.PRNGKey(0))
        _, m1 = train_step(state, batch)

        mesh = make_mesh(MeshConfig(dp=4, tp=2))
        init_state2, train_step2 = make_train_step(
            cfg, mesh=mesh, rules=tp_rules(), donate=False)
        state2 = init_state2(jax.random.PRNGKey(0))
        _, m2 = train_step2(state2, shard_batch(batch, mesh))
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-3)

    # PR 28 gave names (jax.ad_checkpoint.checkpoint_name) to what the
    # attention kernel makes, for the policy OLMoE's blocks remat under
    # (models/decoder.py KEPT_UNDER_REMAT). gpt's blocks run under none
    # (the gpt2 cells) or under one that reads no names.
    def test_remat_names_lower_to_nothing_where_no_block_is_rematerialised(
            self, monkeypatch):
        """The step the gpt2 cells run (remat off), kernels in it: its
        lowered text holds none of the names and is the text of the same
        step with `checkpoint_name` taken out of models/decoder.py and
        ops/attention.py."""
        import dataclasses

        from ray_tpu.models import decoder
        from ray_tpu.ops import attention

        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        cfg = dataclasses.replace(GPTConfig.tiny(), remat=False)
        tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)

        def lowered_text():
            init_state, step = make_train_step(cfg)
            state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
            text = step.trace(state, (tok, tok)).lower(
                lowering_platforms=("tpu",)).as_text()
            # jax numbers a module's private functions (@_take_123) from
            # a counter that every lowering of the process advances
            return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

        named = []
        for module in (attention, decoder):
            monkeypatch.setattr(
                module, "checkpoint_name",
                lambda x, name: named.append(name) or checkpoint_name(x, name))
        with_names = lowered_text()
        assert set(named) == {n for n in decoder.KEPT_UNDER_REMAT
                              if "attention" in n}
        assert len(named) == 6 * cfg.n_layers
        assert "tpu_custom_call" in with_names
        assert not any(n in with_names for n in decoder.KEPT_UNDER_REMAT)
        for module in (attention, decoder):
            monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
        assert lowered_text() == with_names

    def test_remat_changes_no_loss_or_gradient(self):
        """gpt's policy keeps matmul outputs and reads no names. Run
        operation by operation (tests/test_olmoe.py says why): equal, not
        close."""
        import dataclasses
        on = GPTConfig.tiny()
        off = dataclasses.replace(on, remat=False)
        assert on.remat and on.decoder().remat is not None
        params = gpt_init(jax.random.PRNGKey(0), on)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    on.vocab_size)
        batch = (tokens, jnp.roll(tokens, -1, axis=1))
        loss_on, grads_on = jax.value_and_grad(gpt_loss)(params, batch, on)
        loss_off, grads_off = jax.value_and_grad(gpt_loss)(params, batch, off)
        assert float(loss_on) == float(loss_off)
        for a, b in zip(jax.tree.leaves(grads_on), jax.tree.leaves(grads_off),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__
        fn, args = __graft_entry__.entry()
        out = jax.jit(fn)(*args)
        assert out.ndim == 3

    def test_dryrun_multichip(self):
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)


class TestAttentionPlan:
    """attention_plan: the sizes the kernels run at and the sub-blocks a
    head computes, masks and skips (the kernels' loop bounds come from the
    rule that counts them)."""

    @staticmethod
    def _brute(seq_len, sub):
        """(computed, masked, skipped) by looking at every position."""
        vis = np.tril(np.ones((seq_len, seq_len), bool))
        n = seq_len // sub
        tiles = vis.reshape(n, sub, n, sub).transpose(0, 2, 1, 3)
        some, every = tiles.any((2, 3)), tiles.all((2, 3))
        return (int(some.sum()), int((some & ~every).sum()),
                int((~some).sum()))

    @pytest.mark.parametrize("seq_len,head_dim,share", [
        (1024, 64, 0.625), (2048, 64, 0.5625), (4096, 128, 0.532)])
    def test_causal_plan_works_the_triangle(self, seq_len, head_dim, share):
        from ray_tpu.ops.attention import attention_plan
        plan = attention_plan(seq_len, head_dim, True, jnp.bfloat16)
        assert plan.executed_share <= share
        for kernel in (plan.fwd, plan.dq, plan.dkv):
            n = seq_len // kernel.sub
            assert (kernel.computed, kernel.masked, kernel.skipped) == \
                self._brute(seq_len, kernel.sub)
            assert kernel.computed + kernel.skipped == n * n
            assert kernel.masked == n          # the diagonal's own

    @pytest.mark.parametrize("seq_len,head_dim", [(1024, 64), (4096, 128)])
    def test_non_causal_plan_computes_all_and_masks_none(self, seq_len,
                                                         head_dim):
        from ray_tpu.ops.attention import attention_plan
        plan = attention_plan(seq_len, head_dim, False, jnp.bfloat16)
        assert plan.executed_share == 1.0
        for kernel in (plan.fwd, plan.dq, plan.dkv):
            assert kernel.computed == (seq_len // kernel.sub) ** 2
            assert kernel.masked == kernel.skipped == 0

    @pytest.mark.parametrize("head_dim,dtype", [
        (64, jnp.bfloat16), (128, jnp.bfloat16), (128, jnp.float32)])
    def test_every_tileable_sequence_gets_a_legal_plan(self, head_dim,
                                                       dtype):
        from ray_tpu.ops.attention import VMEM_BUDGET, attention_plan
        for seq_len in range(128, 8192 + 1, 128):
            plan = attention_plan(seq_len, head_dim, True, dtype)
            assert plan.vmem_budget == VMEM_BUDGET
            for kernel in (plan.fwd, plan.dq, plan.dkv):
                assert kernel.sub % 128 == 0, (seq_len, kernel)
                assert kernel.block % kernel.sub == 0, (seq_len, kernel)
                assert kernel.swept % kernel.block == 0, (seq_len, kernel)
                assert seq_len % kernel.swept == 0, (seq_len, kernel)
                assert kernel.vmem_bytes <= VMEM_BUDGET, (seq_len, kernel)

    def test_untileable_sequence_is_refused(self):
        from ray_tpu.ops.attention import attention_plan
        with pytest.raises(ValueError, match="multiples of 128"):
            attention_plan(197, 64, False)


class TestFlashKernelInterpret:
    """The actual Pallas kernels (fwd + blockwise flash-2 backward) in
    interpreter mode — the SURVEY §4 CPU-mirror of the on-TPU path."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")

    # (shape, VMEM budget or None for the module's own). The plans: two
    # strips of 128 in one block; one block of 1,024 in 8 strips, 36
    # sub-blocks computed and 8 masked; 4 strips at head_dim 128, whose
    # scale is not a power of two; 3 strips; and, under a budget that
    # whole sequences do not fit, the swept side in blocks on the grid
    # (all three 128 against 512) round the same loops.
    SHAPES = [((1, 2, 256, 64), None), ((1, 3, 1024, 64), None),
              ((1, 3, 512, 128), None), ((3, 1, 384, 64), None),
              ((1, 3, 1024, 64), 1_500_000)]
    shapes = pytest.mark.parametrize(
        "shape,budget", SHAPES,
        ids=["256x64", "1024x64", "512x128", "384x64", "1024x64-grid"])

    @staticmethod
    def _budget(monkeypatch, shape, budget):
        from ray_tpu.ops import attention
        if budget is not None:
            monkeypatch.setattr(attention, "VMEM_BUDGET", budget)
        plan = attention.attention_plan(*shape[-2:], True, jnp.float32)
        on_grid = plan.fwd.swept < shape[-2] and \
            plan.dkv.swept < shape[-2]
        assert on_grid == (budget is not None), plan

    @shapes
    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_fwd_matches_reference(self, causal, shape, budget,
                                          monkeypatch):
        self._budget(monkeypatch, shape, budget)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   for kk in ks)
        out = flash_attention(q, k, v, causal, None)
        ref = mha_reference(q, k, v, causal)
        # f32 attention has ~1e-2 absolute noise between equivalent
        # formulations at this scale; the kernel must sit in that band.
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

    @shapes
    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_bwd_matches_reference(self, causal, shape, budget,
                                          monkeypatch):
        self._budget(monkeypatch, shape, budget)
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32)
                   for kk in ks)

        def loss_k(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, None) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            scale = max(1.0, float(jnp.abs(b).max()))
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale,
                atol=6e-3, rtol=6e-3)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("block,swept", [(256, 512), (512, 512)])
    def test_kernels_with_the_swept_side_on_the_grid(self, causal, block,
                                                     swept):
        """Blocks of several strips against a swept side that is not
        resident: the loops' bounds and the triangle's offsets are traced
        values there, and accumulators carry across grid steps."""
        from ray_tpu.ops import attention
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        q, k, v, g = (jax.random.normal(kk, (1, 3, 1024, 64), jnp.float32)
                      for kk in ks)
        plan = attention.KernelPlan(block, swept, 128, 0, 0, 0, 0, 0)
        out, lse = attention._flash_forward(q, k, v, causal, 0.125, plan)
        ref, vjp = jax.vjp(
            lambda *a: mha_reference(*a, causal), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)
        grads = attention._flash_backward(q, k, v, out, lse, g, causal,
                                          0.125, plan, plan)
        for a, b in zip(grads, vjp(g)):
            scale = max(1.0, float(jnp.abs(b).max()))
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale,
                atol=6e-3, rtol=6e-3)

    @pytest.mark.parametrize("t", [256, 257, 255, 384])
    def test_kernel_causality(self, t):
        """Keys and values from position t on reach no output before t,
        with t on a sub-block boundary (256 and 384, at sub-blocks of 128)
        and one position to either side of it."""
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, k, v = (jax.random.normal(kk, (1, 2, 512, 64), jnp.float32)
                   for kk in ks)
        out1 = flash_attention(q, k, v, True, None)
        out2 = flash_attention(q, k.at[:, :, t:].set(99.0),
                               v.at[:, :, t:].set(-99.0), True, None)
        np.testing.assert_array_equal(np.asarray(out1[:, :, :t]),
                                      np.asarray(out2[:, :, :t]))
        assert not np.allclose(np.asarray(out1[:, :, t]),
                               np.asarray(out2[:, :, t]))

    def test_kernels_run_per_shard_under_a_mesh(self):
        """A sharded train step runs the kernels under shard_map over the
        rule table's batch/head axes (a pallas_call cannot be
        auto-partitioned on TPU) and matches the unsharded step."""
        import dataclasses
        cfg = dataclasses.replace(GPTConfig.tiny(), remat=False)
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 128), dtype=np.int32)  # S=128: kernels
        batch = (jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, 1)))
        init1, step1 = make_train_step(cfg, donate=False)
        _, m1 = step1(init1(jax.random.PRNGKey(0)), batch)
        mesh = make_mesh(MeshConfig(dp=4, tp=2))
        init2, step2 = make_train_step(cfg, mesh=mesh, rules=tp_rules(),
                                       donate=False)
        state2 = init2(jax.random.PRNGKey(0))
        sharded = shard_batch(batch, mesh)
        assert "sdy.manual_computation" in step2.lower(
            state2, sharded).as_text()          # shard_map's lowering
        _, m2 = step2(state2, sharded)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-3)

    def test_the_row_residual_shards_like_q_under_the_dp_rule(self):
        """Under `kernel_sharding` by the dp rule (batch over four chips)
        forward and backward lower with lse as [batch, heads, 1, S], which
        `_per_shard`'s one four-entry spec splits as it splits q, and give
        what the unsharded kernels give."""
        from jax.sharding import PartitionSpec as P

        from ray_tpu.ops.attention import kernel_sharding
        ks = jax.random.split(jax.random.PRNGKey(8), 4)
        q, k, v, w = (jax.random.normal(kk, (4, 2, 256, 64), jnp.float32)
                      for kk in ks)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None) * w)

        def grad():     # (a trace of its own each: jit keys on the function)
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        want = grad()(q, k, v)
        mesh = make_mesh(MeshConfig(dp=4, tp=2))
        with kernel_sharding(mesh, P("dp", None, None, None)):
            lowered = grad().lower(q, k, v)
        text = lowered.as_text()
        assert "sdy.manual_computation" in text
        # a chip's share of the residual, as the forward leaves it and the
        # backward takes it
        assert text.count("tensor<1x2x1x256xf32>") >= 2
        assert "256x128xf32" not in text
        got = lowered.compile()(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_kernel_sharding_refuses_a_sequence_split(self):
        from jax.sharding import PartitionSpec as P

        from ray_tpu.ops.attention import kernel_sharding
        with pytest.raises(ValueError, match="whole sequences"):
            with kernel_sharding(make_mesh(MeshConfig(dp=4, sp=2)),
                                 P("dp", None, "sp", None)):
                pass

    def test_kernel_uneven_heads_batch(self):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, (3, 5, 128, 32), jnp.float32)
                   for kk in ks)
        out = flash_attention(q, k, v, True, None)
        ref = mha_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)
