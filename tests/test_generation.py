"""KV-cache generation + LLM serving tests (reference strategy: the
serving engines the reference hosts are tested for decode parity with
full forward; llm pipeline suites)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.models import (GPTConfig, LlamaConfig, MoEConfig, gpt_forward,
                            gpt_init, llama_forward, llama_init,
                            moe_forward, moe_init)
from ray_tpu.models.generate import (
    generate,
    init_cache,
    make_continuous_fns,
    make_generate_fns,
    sample_token,
)

# One decoder body (models/decoder.py) behind all three: what a family
# trains is what it prefills and decodes. (cfg, init, tokens -> logits,
# the prompt greedy decoding starts from)
FAMILIES = {
    "gpt": (GPTConfig.tiny(), gpt_init, gpt_forward, [5, 7, 11, 13]),
    "llama": (LlamaConfig.tiny(), llama_init, llama_forward, [5, 7, 11, 13]),
    # From gpt's prompt the full forward's fifth row has two equal maxima
    # (tokens 114 and 238, bf16 logits): an argmax there compares nothing.
    # From this one every row's maximum leads by >= 0.1.
    "moe": (MoEConfig.tiny(), moe_init,
            lambda params, tokens, cfg: moe_forward(params, tokens, cfg)[0],
            [100, 50, 25, 12]),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    cfg, init, forward, prompt = FAMILIES[request.param]
    return cfg, init(jax.random.PRNGKey(0), cfg), forward, prompt


def _params(cfg, seed=0):
    return gpt_init(jax.random.PRNGKey(seed), cfg)


class TestKVCacheDecode:
    def test_matches_full_forward(self, family):
        cfg, params, forward, prompt = family
        prompt = np.array([prompt], np.int32)
        cached = [int(t[0]) for t in
                  generate(params, cfg, prompt, max_new_tokens=6)]
        seq = prompt.copy()
        full = []
        for _ in range(6):
            logits = forward(params, jnp.asarray(seq), cfg)
            nxt = int(jnp.argmax(logits[0, -1]))
            full.append(nxt)
            seq = np.concatenate([seq, [[nxt]]], axis=1)
        assert cached == full

    def test_prefill_logits_match(self, family):
        cfg, params, forward, _ = family
        prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
        prefill, _ = make_generate_fns(cfg, 16)
        last, _ = prefill(params, prompt, init_cache(cfg, 1, 16))
        ref = forward(params, prompt, cfg)[:, -1, :]
        np.testing.assert_allclose(np.asarray(last), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)

    def test_slots_at_their_own_positions_match_each_alone(self, family):
        """Continuous batching's per-row start_pos: two slots holding
        prompts of different lengths decode together to the logits each
        sequence gets alone at a scalar position."""
        cfg, params, _, _ = family
        prompts = [[3, 1, 4, 1, 5, 9, 2], [6, 5, 3]]
        steps, max_len = 3, 16
        prefill, decode_step = make_generate_fns(cfg, max_len)
        alone = []
        for p in prompts:
            logits, cache = prefill(params, jnp.asarray([p], jnp.int32),
                                    init_cache(cfg, 1, max_len))
            rows = [logits[0]]
            for i in range(steps):
                logits, cache = decode_step(
                    params, jnp.argmax(logits, -1), len(p) + i, cache)
                rows.append(logits[0])
            alone.append(rows)
        insert, decode_batch = make_continuous_fns(cfg, max_len, 2)
        cache = init_cache(cfg, 2, max_len)
        first = []
        for slot, p in enumerate(prompts):
            padded = jnp.asarray([p + [0] * (8 - len(p))], jnp.int32)
            logits, cache = insert(params, padded, cache, slot, len(p))
            first.append(logits)
        together = [jnp.stack(first)]
        pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
        for i in range(steps):
            logits, cache = decode_batch(
                params, jnp.argmax(together[-1], -1), pos + i, cache)
            together.append(logits)
        for slot in range(2):
            for i in range(steps + 1):
                np.testing.assert_allclose(
                    np.asarray(together[i][slot]),
                    np.asarray(alone[slot][i]), rtol=2e-2, atol=2e-2)

    def test_cache_holds_the_kv_heads_only(self):
        cfg = LlamaConfig.tiny()
        assert cfg.n_kv_heads < cfg.n_heads
        cache = init_cache(cfg, 3, 16)
        assert len(cache) == cfg.n_layers
        assert all(layer[kv].shape == (3, cfg.n_kv_heads, 16, cfg.head_dim)
                   and layer[kv].dtype == cfg.dtype
                   for layer in cache for kv in ("k", "v"))

    def test_batched_generation(self):
        cfg = GPTConfig.tiny()
        params = _params(cfg)
        prompt = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
        steps = list(generate(params, cfg, prompt, max_new_tokens=4))
        assert len(steps) == 4
        assert all(t.shape == (2,) for t in steps)

    def test_temperature_sampling_shape(self):
        logits = jnp.zeros((2, 10))
        tok = sample_token(logits, jax.random.PRNGKey(0),
                           temperature=1.0)
        assert tok.shape == (2,)
        greedy = sample_token(logits.at[:, 3].set(5.0), None, 0.0)
        assert list(np.asarray(greedy)) == [3, 3]


class TestLLMServing:
    def test_engine_stream_and_complete(self):
        from ray_tpu.llm import ByteTokenizer, LLMEngine

        tok = ByteTokenizer()
        assert tok.decode(tok.encode("hello")[1:]) == "hello"
        eng = LLMEngine()
        # Non-byte tokens (BOS) and partial UTF-8 sequences yield no
        # chunk, so at most one fragment per generated token.
        chunks = list(eng.stream("ab", max_new_tokens=3))
        assert len(chunks) <= 3
        text = eng.complete("ab", max_new_tokens=3)
        assert isinstance(text, str)
        # multi-byte output decodes correctly across token boundaries
        class FixedEngine(LLMEngine):
            def stream(self, prompt, max_new_tokens=64, temperature=0.0):
                import codecs
                dec = codecs.getincrementaldecoder("utf-8")(
                    errors="replace")
                for t in b"\xc3\xa9":  # 'é'
                    piece = dec.decode(bytes([t]))
                    if piece:
                        yield piece

        assert "".join(FixedEngine().stream("x")) == "é"

    def test_serve_app(self, ray_start_shared):
        import json
        import urllib.request

        from ray_tpu import serve
        from ray_tpu.llm import build_llm_app

        serve.start()
        try:
            serve.run(build_llm_app(), name="llm")
            addr = serve.proxy_address()
            body = json.dumps({"prompt": "ab", "max_tokens": 2}).encode()
            r = urllib.request.urlopen(f"{addr}/", data=body, timeout=120)
            assert "text" in json.loads(r.read())
            req = urllib.request.Request(
                f"{addr}/", data=json.dumps(
                    {"prompt": "ab", "max_tokens": 2,
                     "stream": True}).encode())
            urllib.request.urlopen(req, timeout=120).read()
        finally:
            serve.shutdown()
