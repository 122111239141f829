"""Online LLM serving: KV-cache decode streamed through Serve.

Parity role: the reference serves LLMs by deploying external engines
(vLLM) on its actors and streaming tokens through Serve's response path;
here the engine is native — models.generate's jitted prefill/decode
steps inside a Serve replica, tokens streamed to clients chunk by chunk
(Serve's streaming response path). `num_tpus=1` in the deployment's
ray_actor_options pins a chip per replica.

Zero-egress tokenizer: a byte-level vocabulary (ids 0-255 + BOS) so the
demo runs without downloaded vocabularies; swap `tokenizer=` for a real
one in production.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, Optional

BOS = 256


class RequestTiming:
    """One request's clock readings, as unix seconds (the clock of a
    profile and of util/tracing.py's request spans), written by the
    engine as the request runs."""

    __slots__ = ("submit_unix", "admit_unix", "first_token_unix",
                 "done_unix", "tokens", "finish_reason")

    def __init__(self):
        self.submit_unix = time.time()
        self.admit_unix: Optional[float] = None
        self.first_token_unix: Optional[float] = None
        self.done_unix: Optional[float] = None
        self.tokens = 0
        self.finish_reason: Optional[str] = None

    def as_dict(self) -> Dict[str, Any]:
        """The reply's `timing`: seconds from submit until a slot took
        the request, until its first token, until it ended; tokens
        generated; why it ended ("length", "stop", "max_len", "error",
        "closed"). None where the request did not get that far."""
        def since(t):
            return None if t is None else t - self.submit_unix
        return {"submit_unix": self.submit_unix,
                "queued_s": since(self.admit_unix),
                "first_token_s": since(self.first_token_unix),
                "done_s": since(self.done_unix),
                "tokens": self.tokens,
                "finish_reason": self.finish_reason}


class TokenStream:
    """Iterator of one request's decoded text pieces, with its timing."""

    def __init__(self, pieces: Iterator[str], timing: RequestTiming):
        self._pieces = pieces
        self.timing = timing

    def __iter__(self):
        return self._pieces

    def __next__(self) -> str:
        return next(self._pieces)


class ByteTokenizer:
    """Byte-level tokenizer (vocab 257: bytes + BOS)."""

    vocab_size = 257

    def encode(self, text: str):
        return [BOS] + list(text.encode("utf-8"))

    def decode(self, ids) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode(
            "utf-8", errors="replace")


class LLMEngine:
    """Jitted prefill + decode wrapper around a GPT-family model
    (construct once per replica; generation streams tokens)."""

    def __init__(self, cfg=None, params=None, tokenizer=None,
                 seed: int = 0):
        import jax

        from ..models import GPTConfig, gpt_init

        self.tokenizer = tokenizer or ByteTokenizer()
        self.cfg = cfg or GPTConfig(
            vocab_size=max(ByteTokenizer.vocab_size, 272),
            d_model=256, n_heads=8, n_layers=4, d_ff=1024,
            max_seq_len=512)
        self.params = params if params is not None else gpt_init(
            jax.random.PRNGKey(seed), self.cfg)

    def stream(self, prompt: str, max_new_tokens: int = 64,
               temperature: float = 0.0) -> TokenStream:
        """Yield decoded text fragments token by token. Multi-byte
        UTF-8 sequences are buffered across tokens (an incremental
        decoder), and over-long prompts keep their TAIL so the model
        conditions on the most recent context."""
        timing = RequestTiming()
        return TokenStream(
            self._pieces(prompt, max_new_tokens, temperature, timing),
            timing)

    def _pieces(self, prompt, max_new_tokens, temperature,
                timing: RequestTiming) -> Iterator[str]:
        import codecs

        import numpy as np

        from ..models.generate import generate

        # No queue and no slots: the request runs in its caller's thread.
        timing.admit_unix = time.time()
        encoded = self.tokenizer.encode(prompt)
        # Leave room for at least one generated token.
        keep = self.cfg.max_seq_len - max(1, min(max_new_tokens, 16))
        if len(encoded) > keep:
            encoded = encoded[-keep:]
        ids = np.asarray([encoded], np.int32)
        budget = self.cfg.max_seq_len - ids.shape[1]
        decoder = codecs.getincrementaldecoder("utf-8")(errors="replace")
        for token in generate(self.params, self.cfg, ids,
                              max_new_tokens=min(max_new_tokens, budget),
                              temperature=temperature):
            t = int(token[0])
            timing.tokens += 1
            if timing.tokens == 1:
                timing.first_token_unix = time.time()
            piece = decoder.decode(bytes([t])) if 0 <= t < 256 else ""
            if piece:
                yield piece
        timing.done_unix = time.time()
        timing.finish_reason = "length"
        tail = decoder.decode(b"", final=True)
        if tail:
            yield tail

    def complete(self, prompt: str, max_new_tokens: int = 64,
                 temperature: float = 0.0) -> str:
        return "".join(self.stream(prompt, max_new_tokens, temperature))


def build_llm_app(cfg=None, params=None, *, num_replicas: int = 1,
                  num_tpus: float = 0, continuous_batching: bool = False,
                  max_batch: int = 8):
    """Serve application: POST {"prompt": ..., "max_tokens": ...,
    "stream": bool} — streaming responses ride Serve's chunked path;
    a non-streaming reply is {"text", "device", "engine_steps",
    "timing"} (`timing`: RequestTiming.as_dict).

    ``continuous_batching=True`` backs each replica with ONE shared
    ContinuousBatchingEngine (llm/continuous.py): concurrent requests
    decode together in a slot-reuse KV batch, so a late request joins
    the running decode instead of queueing behind it."""
    from .. import serve

    actor_opts: Dict[str, Any] = {}
    if num_tpus:
        actor_opts["num_tpus"] = num_tpus

    @serve.deployment(num_replicas=num_replicas,
                      ray_actor_options=actor_opts or None,
                      max_ongoing_requests=max(16, 2 * max_batch))
    class LLMServer:
        def __init__(self):
            if continuous_batching:
                from .continuous import ContinuousBatchingEngine
                self.engine = ContinuousBatchingEngine(
                    cfg=cfg, params=params, max_batch=max_batch)
                self._stream = self.engine.submit
            else:
                self.engine = LLMEngine(cfg=cfg, params=params)
                self._stream = self.engine.stream
            # Which device this replica computes on, as its own process
            # sees it: every reply carries it, so a client (and the chip
            # smoke) can tell a chip replica from one that fell to CPU.
            import os

            import jax

            from .. import api
            dev = jax.tree.leaves(self.engine.params)[0].devices()
            self._device = {
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "param_device_ids": sorted(d.id for d in dev),
                "local_device_count": jax.local_device_count(),
                "tpu_ids": api.get_tpu_ids(),
                "pid": os.getpid(),
            }

        def _lazy_stream(self, prompt, max_tokens, temperature):
            # Defer the submit to first iteration: the serve replica's
            # dynamic-generator handshake re-runs the handler once on
            # the first stream=True request (StreamingResponseRequired
            # retry), and an EAGER submit there would enqueue a second,
            # abandoned copy that burns a continuous-batching KV slot
            # for its whole token budget.
            yield from self._stream(prompt, max_tokens, temperature)

        def __call__(self, request):
            body = request.get("body") or {}
            prompt = str(body.get("prompt", ""))
            try:
                max_tokens = max(1, min(int(body.get("max_tokens", 32)),
                                        self.engine.cfg.max_seq_len))
                temperature = max(0.0,
                                  float(body.get("temperature", 0.0)))
            except (TypeError, ValueError):
                return {"error": "max_tokens must be an int and "
                        "temperature a float"}
            if body.get("stream"):
                return self._lazy_stream(prompt, max_tokens,
                                         temperature)
            stream = self._stream(prompt, max_tokens, temperature)
            text = "".join(stream)
            return {"text": text, "device": self._device,
                    "engine_steps": getattr(self.engine, "steps", None),
                    "timing": stream.timing.as_dict()}

        def generate_stream(self, prompt: str, max_tokens: int = 32,
                            temperature: float = 0.0):
            yield from self._stream(prompt, max_tokens, temperature)

        def engine_counters(self):
            """The continuous engine's always-on counters and the timing
            of its latest finished requests (llm/continuous.py); None
            from the sequential engine, which has no loop to count."""
            if not continuous_batching:
                return None
            return {**self.engine.counters(),
                    "finished": list(self.engine.finished)}

    return LLMServer.bind()
