"""Continuous batching for the native generation engine.

Net-new TPU-native capability (the reference delegates this to vLLM on
its actors): late requests JOIN a running decode batch — a free KV-cache
slot is prefilled while the other slots keep decoding — and slots are
reused the moment a stream finishes (EOS / token budget), so aggregate
decode throughput approaches batch-width tokens per step instead of one
per step per sequential request. Static shapes throughout: one XLA
compile per prompt-length bucket plus one batched decode compile; slot
occupancy changes never trigger recompilation (vLLM-style continuous
batching re-expressed for XLA's compile-once model).

Driven by a single decode thread per engine (a Serve replica owns one
engine; its requests share the batch). Thread-safe submit() returns an
iterator of decoded text pieces.

What the loop does with its time is on two records. Spans on the
profiler's clock, `ray_tpu.engine.<phase>` (util/profiling.py
HOST_SPANS), name the host's part of every gap in a device trace. The
same phases are counted always, beside `steps`: `counters()` gives
seconds and entries per phase, occupied slots summed over decode steps,
tokens out, and requests by finish reason; `finished` holds the timing
of the latest requests (wait for a slot, first token, done).
"""

from __future__ import annotations

import collections
import contextlib
import queue
import threading
import time
from typing import Dict, List, Optional

from ..util.profiling import annotate
from .serving import RequestTiming, TokenStream

_SENTINEL = object()

# The loop's phases, disjoint in time: each is a span and a counter.
PHASES = ("admit", "prefill", "decode", "fetch", "sample", "idle")


class _Request:
    __slots__ = ("ids", "max_new", "temperature", "out", "stop_token",
                 "seed", "timing")

    def __init__(self, ids, max_new, temperature, stop_token, seed):
        self.ids = ids
        self.max_new = max_new
        self.temperature = temperature
        self.stop_token = stop_token
        self.seed = seed
        self.out: "queue.Queue" = queue.Queue()
        self.timing = RequestTiming()


class _Slot:
    __slots__ = ("req", "pos", "emitted", "rng", "last_token")

    def __init__(self, req: _Request, pos: int, rng):
        self.req = req
        self.pos = pos          # next decode position (== tokens so far)
        self.emitted = 0
        self.rng = rng
        self.last_token = 0


class ContinuousBatchingEngine:
    """Shared-batch KV-cache decode with slot insertion/reuse."""

    def __init__(self, cfg=None, params=None, tokenizer=None,
                 max_batch: int = 8, max_len: Optional[int] = None,
                 seed: int = 0):
        import jax

        from ..models import GPTConfig, gpt_init
        from ..models.generate import init_cache, make_continuous_fns
        from .serving import ByteTokenizer

        self.tokenizer = tokenizer or ByteTokenizer()
        self.cfg = cfg or GPTConfig(
            vocab_size=max(ByteTokenizer.vocab_size, 272),
            d_model=256, n_heads=8, n_layers=4, d_ff=1024,
            max_seq_len=512)
        self.params = params if params is not None else gpt_init(
            jax.random.PRNGKey(seed), self.cfg)
        self.max_batch = int(max_batch)
        self.max_len = int(max_len or self.cfg.max_seq_len)
        self._prefill, self._decode = make_continuous_fns(
            self.cfg, self.max_len, self.max_batch)
        self._cache = init_cache(self.cfg, self.max_batch, self.max_len)
        self._slots: List[Optional[_Slot]] = [None] * self.max_batch
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # Step counter — tests assert late requests really joined a
        # RUNNING batch (their first token decoded at a step > 0 while
        # another slot was mid-stream).
        self.steps = 0
        # Written by the decode thread only; read through counters().
        self.phase_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.phase_n: Dict[str, int] = dict.fromkeys(PHASES, 0)
        self.slot_steps = 0     # occupied slots, summed over decode steps
        self.tokens_out = 0     # = slot_steps + requests admitted
        self.admitted = 0
        self.finish_reasons: Dict[str, int] = {}
        self.finished: collections.deque = collections.deque(maxlen=4096)

    # -- public api --------------------------------------------------------
    def submit(self, prompt: str, max_new_tokens: int = 32,
               temperature: float = 0.0,
               stop_token: Optional[int] = None,
               seed: int = 0) -> TokenStream:
        """Enqueue a request; returns an iterator of decoded text
        pieces whose `timing` fills in as the request runs. The request
        joins the running batch as soon as a slot frees (or immediately
        when one is open)."""
        import codecs

        encoded = self.tokenizer.encode(prompt)
        keep = self.max_len - max(1, min(max_new_tokens, 16))
        if len(encoded) > keep:
            encoded = encoded[-keep:]
        budget = min(max_new_tokens, self.max_len - len(encoded))
        req = _Request(encoded, max(1, budget), float(temperature),
                       stop_token, seed)
        with self._lock:
            if self._closed:
                raise RuntimeError("engine closed")
            self._pending.put(req)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name="cb-decode")
                self._thread.start()
        self._wake.set()

        def _stream():
            decoder = codecs.getincrementaldecoder("utf-8")(
                errors="replace")
            while True:
                item = req.out.get()
                if item is _SENTINEL:
                    break
                if isinstance(item, BaseException):
                    raise item
                if 0 <= item < 256:
                    piece = decoder.decode(bytes([item]))
                    if piece:
                        yield piece
            tail = decoder.decode(b"", final=True)
            if tail:
                yield tail
        return TokenStream(_stream(), req.timing)

    def complete(self, prompt: str, max_new_tokens: int = 32,
                 temperature: float = 0.0, **kw) -> str:
        return "".join(self.submit(prompt, max_new_tokens, temperature,
                                   **kw))

    def close(self):
        with self._lock:
            self._closed = True
        self._wake.set()

    def counters(self) -> Dict:
        """A copy of the always-on counters (the decode thread writes
        them unlocked: a reader sees each number whole, and the set at
        most one step apart)."""
        return {"steps": self.steps, "phase_s": dict(self.phase_s),
                "phase_n": dict(self.phase_n),
                "slot_steps": self.slot_steps,
                "tokens_out": self.tokens_out, "admitted": self.admitted,
                "finish_reasons": dict(self.finish_reasons)}

    @contextlib.contextmanager
    def _phase(self, name: str):
        t0 = time.perf_counter()
        try:
            with annotate("ray_tpu.engine." + name):
                yield
        finally:
            self.phase_s[name] += time.perf_counter() - t0
            self.phase_n[name] += 1

    def _finish(self, req: _Request, reason: str) -> None:
        t = req.timing
        t.done_unix = time.time()
        t.finish_reason = reason
        self.finish_reasons[reason] = self.finish_reasons.get(reason, 0) + 1
        self.finished.append(t.as_dict())

    # -- decode loop -------------------------------------------------------
    def _admit(self) -> None:
        """Prefill pending requests into free slots (called between
        decode steps — this is the 'late request joins a running
        batch' moment)."""
        import numpy as np

        from ..models.generate import _bucket_len

        for i in range(self.max_batch):
            if self._slots[i] is not None:
                continue
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return
            with self._phase("admit"):
                req.timing.admit_unix = time.time()
                self.admitted += 1
                true_len = len(req.ids)
                bucket = min(_bucket_len(true_len, self.max_len),
                             self.max_len)
                padded = req.ids + [0] * (bucket - true_len)
                tokens = np.asarray([padded], np.int32)
            try:
                with self._phase("prefill"):
                    last, self._cache = self._prefill(
                        self.params, tokens, self._cache, i, true_len)
                    last = np.asarray(last)
            except BaseException as e:  # noqa: BLE001
                # The request is already popped from _pending and holds
                # no slot: _fail_all can't see it, so a prefill failure
                # (OOM, compile error) must terminate ITS stream here or
                # submit()'s consumer blocks forever on req.out.
                self._finish(req, "error")
                req.out.put(e)
                req.out.put(_SENTINEL)
                raise
            with self._phase("sample"):
                rng = np.random.default_rng(req.seed)
                slot = _Slot(req, true_len, rng)
                self._slots[i] = slot
                self._emit(i, last)

    def _emit(self, i: int, logits) -> None:
        """Sample one token for slot i from host-side logits; push to
        the request's stream; retire the slot at EOS/budget. Host-side
        sampling keeps per-request temperature/seed without burning a
        compile per combination.

        Position bookkeeping mirrors models.generate: slot.pos is where
        the just-sampled token WILL be written by the next decode step
        (== tokens currently in the cache); the loop advances it after
        the decode that consumes the token."""
        import numpy as np

        slot = self._slots[i]
        req = slot.req
        if req.temperature <= 0.0:
            token = int(np.argmax(logits))
            # argmax lands on a NaN when there is one, so one scalar
            # read tells whether the row was finite; a broken forward
            # must fail the streams, not emit token 0 forever.
            if not np.isfinite(logits[token]):
                raise FloatingPointError(
                    f"non-finite logits in slot {i} at position "
                    f"{slot.pos}")
        else:
            z = logits.astype(np.float64) / req.temperature
            z -= z.max()
            p = np.exp(z)
            p /= p.sum()
            token = int(slot.rng.choice(len(p), p=p))
        req.out.put(token)
        slot.emitted += 1
        slot.last_token = token
        self.tokens_out += 1
        req.timing.tokens = slot.emitted
        if slot.emitted == 1:
            req.timing.first_token_unix = time.time()
        if req.stop_token is not None and token == req.stop_token:
            reason = "stop"
        elif slot.emitted >= req.max_new:
            reason = "length"
        elif slot.pos >= self.max_len:
            reason = "max_len"
        else:
            return
        self._finish(req, reason)
        req.out.put(_SENTINEL)
        self._slots[i] = None   # slot free: next _admit reuses it

    def _fail_all(self, exc: Optional[BaseException]) -> None:
        """Terminate every active and pending stream; exc is re-raised
        in consumers when given, else the streams just end."""
        reason = "error" if exc is not None else "closed"
        for i, s in enumerate(self._slots):
            if s is not None:
                self._finish(s.req, reason)
                if exc is not None:
                    s.req.out.put(exc)
                s.req.out.put(_SENTINEL)
                self._slots[i] = None
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return
            self._finish(req, reason)
            if exc is not None:
                req.out.put(exc)
            req.out.put(_SENTINEL)

    def _loop(self) -> None:
        import numpy as np
        try:
            while True:
                self._admit()
                active = [i for i in range(self.max_batch)
                          if self._slots[i] is not None]
                if not active:
                    with self._lock:
                        if self._closed:
                            # Atomic with submit()'s check+enqueue:
                            # drain anything that raced in so no
                            # consumer blocks forever.
                            self._fail_all(
                                RuntimeError("engine closed"))
                            return
                    with self._phase("idle"):
                        self._wake.wait(timeout=0.5)
                        self._wake.clear()
                    continue
                with self._phase("decode"):
                    tokens = np.zeros(self.max_batch, np.int32)
                    pos = np.zeros(self.max_batch, np.int32)
                    for i in active:
                        slot = self._slots[i]
                        tokens[i] = slot.last_token
                        pos[i] = slot.pos  # where this token is written
                    logits, self._cache = self._decode(
                        self.params, tokens, pos, self._cache)
                    self.steps += 1
                    self.slot_steps += len(active)
                with self._phase("fetch"):
                    logits_np = np.asarray(logits)
                with self._phase("sample"):
                    for i in active:
                        slot = self._slots[i]
                        if slot is not None:
                            slot.pos += 1  # the decode wrote at old pos
                            self._emit(i, logits_np[i])
        except BaseException as e:  # noqa: BLE001
            # The engine is dead: close it so later submit() raises
            # instead of enqueueing into a loop that no longer runs,
            # and fail EVERY stream — active and still-pending — with
            # the error (a pending request ending silently would look
            # like an empty completion).
            with self._lock:
                self._closed = True
                self._fail_all(e)
