"""Continuous batching (VERDICT r4 next #8): late requests join a
RUNNING decode batch, slots are reused on completion, and 8 concurrent
streams share decode steps.

Correctness anchor: with temperature 0, the continuous engine's output
must be byte-identical to models.generate's sequential path for the
same params (same formulas — per-slot positions and masks are the only
difference)."""

import pytest

import jax

from ray_tpu.llm.continuous import ContinuousBatchingEngine
from ray_tpu.llm.serving import ByteTokenizer, LLMEngine
from ray_tpu.models import GPTConfig, gpt_init


@pytest.fixture(scope="module")
def small_setup():
    cfg = GPTConfig(vocab_size=272, d_model=64, n_heads=4, n_layers=2,
                    d_ff=128, max_seq_len=256)
    params = gpt_init(jax.random.PRNGKey(7), cfg)
    return cfg, params


@pytest.fixture()
def engine(small_setup):
    cfg, params = small_setup
    eng = ContinuousBatchingEngine(cfg=cfg, params=params, max_batch=4)
    yield eng
    eng.close()


def _reference(cfg, params, prompt, n):
    return LLMEngine(cfg=cfg, params=params).complete(
        prompt, max_new_tokens=n, temperature=0.0)


class TestCorrectness:
    def test_matches_sequential_reference(self, small_setup, engine):
        cfg, params = small_setup
        out = engine.complete("hello world", 24, 0.0)
        ref = _reference(cfg, params, "hello world", 24)
        assert out == ref

    def test_multiple_prompts_all_match(self, small_setup, engine):
        cfg, params = small_setup
        prompts = ["alpha", "the quick brown fox", "z", "data 123"]
        streams = [engine.submit(p, 16, 0.0) for p in prompts]
        outs = ["".join(s) for s in streams]
        for p, o in zip(prompts, outs):
            assert o == _reference(cfg, params, p, 16), p

    def test_slot_reuse_more_requests_than_slots(self, small_setup,
                                                 engine):
        cfg, params = small_setup
        prompts = [f"prompt {i}" for i in range(10)]  # > max_batch=4
        streams = [engine.submit(p, 8, 0.0) for p in prompts]
        outs = ["".join(s) for s in streams]
        for p, o in zip(prompts, outs):
            assert o == _reference(cfg, params, p, 8), p


class TestLateJoin:
    def test_late_request_joins_running_decode(self, small_setup,
                                               engine):
        cfg, params = small_setup
        long_stream = engine.submit("long running request", 48, 0.0)
        first = []
        # Consume a few tokens so the batch is demonstrably mid-decode.
        it = iter(long_stream)
        for _ in range(6):
            first.append(next(it))
        steps_before = engine.steps
        assert steps_before > 0
        late = "".join(engine.submit("late arrival", 8, 0.0))
        rest = "".join(it)
        # The long request is unaffected by the mid-flight join...
        assert "".join(first) + rest == _reference(
            cfg, params, "long running request", 48)
        # ...the late one is correct...
        assert late == _reference(cfg, params, "late arrival", 8)
        # ...and it decoded on steps AFTER the batch was already
        # running (it joined, it did not restart the engine).
        assert engine.steps > steps_before


class TestThroughput:
    def test_concurrent_streams_share_decode_steps(self, small_setup):
        """Eight concurrent streams take at most half the decode steps of
        eight sequential ones — a count the engine makes itself, not a
        wall-clock ratio (speed belongs to a chip cell, ROADMAP A5)."""
        cfg, params = small_setup
        n_streams, n_tokens = 8, 24
        prompts = [f"stream number {i}" for i in range(n_streams)]
        eng = ContinuousBatchingEngine(cfg=cfg, params=params,
                                       max_batch=n_streams)
        try:
            eng.complete("warmup", n_tokens, 0.0)  # compile
            before = eng.steps
            streams = [eng.submit(p, n_tokens, 0.0) for p in prompts]
            outs = ["".join(s) for s in streams]
            wave_steps = eng.steps - before
        finally:
            eng.close()
        for out, p in zip(outs, prompts):
            assert out == _reference(cfg, params, p, n_tokens), p
        sequential_steps = n_streams * (n_tokens - 1)
        assert n_tokens - 1 <= wave_steps <= sequential_steps // 2, (
            f"{wave_steps} decode steps for {n_streams} concurrent "
            f"streams; sequential takes {sequential_steps}")


class TestServeIntegration:
    def test_serve_app_with_continuous_batching(self, ray_start_shared,
                                                small_setup):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm import build_llm_app

        cfg, params = small_setup
        serve.start()
        app = build_llm_app(cfg=cfg, params=params,
                            continuous_batching=True, max_batch=4)
        serve.run(app, name="cbllm", route_prefix="/cbllm")
        try:
            h = serve.get_deployment_handle("LLMServer", "cbllm")
            out = h.remote({"body": {"prompt": "hi", "max_tokens": 8}}
                           ).result(timeout_s=120)
            assert out["text"] == _reference(cfg, params, "hi", 8)
        finally:
            # Full shutdown (not just delete): later serve tests in the
            # shared session boot their own proxy + controller.
            serve.shutdown()
