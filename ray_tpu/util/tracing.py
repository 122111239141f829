"""Distributed tracing: a first-class citizen of the telemetry plane.

Reference parity: python/ray/util/tracing/tracing_helper.py — the
reference injects OpenTelemetry spans around task/actor submission and
execution and propagates span context *inside task specs*
(_DictPropagator:165, span decorators :195+), and aggregates per-task
events in the GCS task manager (SURVEY §2.2, §5). Same design here,
with no OpenTelemetry in it: spans are this module's own records.

Architecture (PR 7 — everything piggybacks on the telemetry plane):

  * **Recording** is a lock + bounded deque append into a process-local
    drop-oldest buffer with an EXACT drop counter — never a syscall,
    never a head round trip (the old ``record_spans`` gcs_request flush
    after every traced task is gone).
  * **Shipping**: workers drain the buffer into the ``TASK_EVENTS``
    frame enqueued right before each completion (worker_proc
    ``_flush_telemetry``), so spans ride the SAME vectored write as the
    TASK_DONE — zero extra syscalls; idle workers drain on the
    TELEMETRY_DRAIN heartbeat nudge. The driver flushes straight into
    the in-process store.
  * **Aggregation**: ``Gcs.telemetry`` keeps bounded per-trace rings
    (``TelemetryStore.record_spans``) with per-trace drop counters and
    a global LRU cap — replacing the old unbounded ``Gcs._spans`` list.
  * **Propagation**: submit spans stamp ``spec.trace_ctx`` (api.py);
    the direct plane carries the context as a compact-wire tail slot
    (traced calls keep the no-arg fast path); the serve proxy speaks
    W3C ``traceparent`` in and out.

Gate discipline: ``tracing.enabled`` is a module attribute (falsy-flag,
like ``telemetry.enabled`` / ``fault.enabled``); every helper that does
tracing work bumps the ``_ops`` counter so the tracing-off hot path is
provably zero-work (counter-based perf_smoke guard). ``enable()``
mirrors the flag into ``RAY_TPU_TRACING`` so spawned daemons, workers,
and serve replicas inherit it.

Usage:
    from ray_tpu.util import tracing
    tracing.enable()
    with tracing.span("ingest", source="s3"):
        ref = f.remote(...)        # submit span + context ride the spec
    tracing.get_trace(trace_id)    # cross-node tree + critical path
    tracing.export_chrome_trace("/tmp/trace.json")

A run's spans (`Run`) are the exception to the gate: a dozen a training
job, not one a request, so they are recorded always, into a list their
owner keeps, and written to the run's own file (train/v2/controller.py).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from . import profiling

_ENV_VAR = "RAY_TPU_TRACING"


def _env_enabled() -> bool:
    return os.environ.get(_ENV_VAR, "0").strip().lower() in (
        "1", "true", "yes", "on")


# Hot-path gate: module attribute looked up as `tracing.enabled` (one
# dict lookup); instrumentation sites check it (or an adopted context)
# before doing ANY tracing work. Default OFF (tracing is opt-in, unlike
# telemetry).
enabled = _env_enabled()

# Counter of tracing-helper invocations in THIS process — the
# perf_smoke guard's counter-based proxy for "the disabled path did no
# tracing work" (same discipline as telemetry.instrument_ops).
_ops = 0

_lock = threading.Lock()
# Bounded drop-oldest span buffer (drained by the worker's telemetry
# flush / the driver's in-process flush). Exact accounting: every
# record beyond capacity since the last drain counts in _dropped once.
_buffer: collections.deque = collections.deque()
_dropped = 0

_current: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace", default=None)   # (trace_id, span_id) or None


def _buffer_cap() -> int:
    from .._private.config import ray_config
    return max(16, int(ray_config.span_buffer_size))


def trace_ops() -> int:
    """Tracing-helper invocations so far (perf_smoke guard)."""
    return _ops


def enable(propagate_env: bool = True) -> None:
    """Turn on tracing in this process (reference:
    ray.init(_tracing_startup_hook=...) switch). With ``propagate_env``
    the flag is mirrored into RAY_TPU_TRACING so spawned daemons and
    workers inherit it."""
    global enabled
    enabled = True
    if propagate_env:
        os.environ[_ENV_VAR] = "1"


def disable(propagate_env: bool = True) -> None:
    global enabled
    enabled = False
    if propagate_env:
        os.environ[_ENV_VAR] = "0"


def is_enabled() -> bool:
    """Tracing is on if enabled process-wide OR a propagated context is
    active in this task (workers trace exactly the requests whose
    driver/proxy had tracing on, without flipping process state)."""
    return enabled or _current.get() is not None


def current_context() -> Optional[Dict[str, str]]:
    """Propagatable context dict of the active span (reference:
    _DictPropagator.inject_current_context)."""
    cur = _current.get()
    if cur is None:
        return None
    return {"trace_id": cur[0], "parent_span_id": cur[1]}


# ---------------------------------------------------------------------------
# W3C traceparent (the serve-proxy wire form of the context)
# ---------------------------------------------------------------------------
def parse_traceparent(header: Optional[str]) -> Optional[Dict[str, str]]:
    """``00-<32hex trace>-<16hex parent>-<2hex flags>`` -> context dict
    (None on anything malformed — a bad client header must never fail
    the request)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None
    return {"trace_id": parts[1], "parent_span_id": parts[2]}


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------
def _record(span: dict) -> None:
    """Buffer one finished span: lock + deque append, drop-oldest with
    an exact counter. NO flush round trip here — spans leave the
    process on the telemetry plane's existing frames."""
    global _dropped
    cap = _buffer_cap()
    with _lock:
        if len(_buffer) >= cap:
            _buffer.popleft()
            _dropped += 1
        _buffer.append(span)


def drain_spans() -> Tuple[List[dict], int]:
    """Pop everything buffered; returns (spans, dropped_since_last).
    Called by the worker's telemetry flush (spans ride the TASK_EVENTS
    frame) and by the driver-side flush below."""
    global _dropped
    if not _buffer and not _dropped:
        return [], 0
    with _lock:
        spans = list(_buffer)
        _buffer.clear()
        dropped, _dropped = _dropped, 0
    return spans, dropped


def flush() -> None:
    """Consumer-path flush: move buffered spans into the head's store.
    On the driver this is an in-process call; in a worker it is ONE
    explicit gcs request (reached only from get_spans/get_trace — the
    task hot path ships spans on the TASK_EVENTS piggyback instead).
    Before init the bounded buffer simply holds."""
    if not _buffer and not _dropped:
        return
    from .._private import state
    node = state.get_node()
    if node is not None:
        spans, dropped = drain_spans()
        if spans or dropped:
            node.gcs.record_spans(spans, dropped=dropped,
                                  node_id=node.node_id.hex(),
                                  worker_id="driver")
        return
    rt = state.current_or_none()
    if rt is None or not hasattr(rt, "gcs_request"):
        return
    spans, dropped = drain_spans()
    if spans or dropped:
        # Stamp THIS worker's identity: the head's generic gcs-op path
        # has no sender context, and an unstamped batch would render
        # under the head node / "driver" in the tree.
        kw = {"spans": spans, "dropped": dropped}
        w = getattr(state, "_worker", None)
        if w is not None:
            kw["node_id"] = w.config.node_id_hex
            kw["worker_id"] = w.config.worker_id.hex()
        try:
            rt.gcs_request("record_spans", **kw)
        except Exception:
            # Bounded loss, surfaced: no silent swallow, no unbounded
            # retry re-queue (the old `_buffer = batch + _buffer` bug).
            import logging
            logging.getLogger(__name__).warning(
                "dropping %d spans: head flush failed", len(spans),
                exc_info=True)


@contextlib.contextmanager
def span(name: str, **attributes: Any):
    """Record a span; nests under the active span, and downstream
    task/actor submissions inside it carry the context remotely."""
    global _ops
    if not is_enabled():
        yield None
        return
    _ops += 1
    cur = _current.get()
    trace_id = cur[0] if cur else uuid.uuid4().hex
    span_id = uuid.uuid4().hex[:16]
    token = _current.set((trace_id, span_id))
    start = time.time()
    error = None
    try:
        yield span_id
    except BaseException as e:
        error = repr(e)
        raise
    finally:
        _current.reset(token)
        _record({
            "name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_span_id": cur[1] if cur else None,
            "start": start, "end": time.time(),
            "attributes": attributes or None, "error": error,
        })


class Run:
    """The spans of one run (a training job from fit() to its Result; a
    driver's init): the same record as `span` writes, under one
    `trace_id`, kept in `spans` by whoever owns the run, so
    `build_trace`, `format_trace` and a chrome trace read them as they
    read a request's. Recorded whether or not tracing is enabled: a run
    has a dozen, and they are what says where its start went. Each is
    also a `profiling.annotate`, so a running `profiling.capture` shows
    it on the device trace's clock. Where tracing is enabled a span also
    goes, as it ends, the way every span goes: to the head's store.

    Parents are given, not taken from the calling context: a run's spans
    cross processes by their ids (`TrainWorker.setup`'s `run_trace`),
    and the calls made inside one are not traced for its sake."""

    def __init__(self, trace_id: Optional[str] = None):
        self.trace_id = trace_id or uuid.uuid4().hex
        self.spans: List[dict] = []

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[str] = None, /,
             **attributes: Any):
        """Yields the record, open (`end` None) and already in `spans`;
        the block may fill `attributes` in."""
        record = {"name": name, "trace_id": self.trace_id,
                  "span_id": uuid.uuid4().hex[:16],
                  "parent_span_id": parent, "start": time.time(),
                  "end": None, "attributes": attributes or None,
                  "error": None}
        self.spans.append(record)
        try:
            with profiling.annotate(name):
                yield record
        except BaseException as e:
            record["error"] = repr(e)
            raise
        finally:
            record["end"] = time.time()
            if enabled:
                _record(dict(record))

    def mark(self, name: str, parent: Optional[str] = None, /,
             **attributes: Any) -> None:
        """A zero-length span: a moment with a name."""
        with self.span(name, parent, **attributes):
            pass

    def snapshot(self) -> List[dict]:
        """Copies of the spans so far, open ones too."""
        return [dict(s) for s in list(self.spans)]


def activate_context(ctx: Optional[Dict[str, str]]):
    """Adopt a propagated context (worker side; reference: extract from
    the task spec before running the user function). Returns a reset
    token or None. Deliberately does NOT flip the process-global enable
    flag: once the context is reset, this worker stops tracing unless
    the next task carries a context too."""
    global _ops
    if not ctx:
        return None
    _ops += 1
    return _current.set((ctx["trace_id"], ctx["parent_span_id"]))


def deactivate_context(token) -> None:
    if token is not None:
        _current.reset(token)


# ---------------------------------------------------------------------------
# collection / consumers (driver side)
# ---------------------------------------------------------------------------
def get_spans(trace_id: Optional[str] = None) -> List[dict]:
    """Spans aggregated in the head's telemetry store (flushing this
    process's buffer first)."""
    flush()
    from .._private import state
    node = state.get_node()
    if node is not None:
        return node.gcs.spans(trace_id)
    rt = state.current_or_none()
    if rt is not None and hasattr(rt, "gcs_request"):
        try:
            # `or []`: local mode answers unknown ops with None.
            return rt.gcs_request("get_spans", trace_id=trace_id) or []
        except Exception:
            return []
    return []


def _duration_s(span: dict) -> float:
    """A span's length; 0 while it is open."""
    if span.get("end") is None:
        return 0.0
    return span["end"] - (span.get("start") or 0.0)


def build_trace(spans: List[dict]) -> dict:
    """Assemble one trace's spans into a tree + critical-path summary.
    Pure function of the span list (unit-testable; get_trace feeds it
    the store's ring)."""
    by_id: Dict[str, dict] = {}
    for s in spans:
        sid = s.get("span_id")
        if sid:
            # First writer wins: a SIGKILL/retry replay of the same
            # span id must not duplicate a node in the tree.
            by_id.setdefault(sid, dict(s, children=[]))
    roots: List[dict] = []
    for node in by_id.values():
        parent = by_id.get(node.get("parent_span_id") or "")
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=lambda c: c.get("start", 0.0))
    roots.sort(key=lambda c: c.get("start", 0.0))

    # Critical path: from the root whose END is latest (a request has
    # one root; a run's file has two, `ray_tpu.init` and the run),
    # descend into the child whose END is latest (the chain the trace's
    # wall time waited on). A span still open (`end` None, in a run's
    # file written while it runs) ends after every closed one.
    def end_of(span):
        end = span.get("end")
        return float("inf") if end is None else end

    path: List[dict] = []
    cur = max(roots, key=end_of) if roots else None
    while cur is not None:
        path.append({
            "name": cur.get("name"), "span_id": cur.get("span_id"),
            "start": cur.get("start"), "end": cur.get("end"),
            "duration_s": round(_duration_s(cur), 6),
            "node_id": cur.get("node_id"),
            "worker_id": cur.get("worker_id"),
            "error": cur.get("error")})
        kids = cur["children"]
        cur = max(kids, key=end_of) if kids else None
    starts = [s.get("start") for s in spans if s.get("start") is not None]
    ends = [s.get("end") for s in spans if s.get("end") is not None]
    return {
        "trace_id": spans[0].get("trace_id") if spans else None,
        "span_count": len(by_id),
        "node_ids": sorted({s.get("node_id") for s in spans
                            if s.get("node_id")}),
        "duration_s": round(max(ends) - min(starts), 6)
        if starts and ends else 0.0,
        "roots": roots,
        "critical_path": path,
    }


def get_trace(trace_id: str) -> dict:
    """Reassemble the cross-node span tree of one trace with a
    critical-path summary (reference: what a Jaeger/Zipkin UI renders
    from the collector; the `ray_tpu trace <id>` CLI prints this)."""
    return build_trace(get_spans(trace_id))


def format_trace(trace: dict) -> str:
    """Human-readable tree of a get_trace() result (the CLI's renderer)."""
    lines = [f"trace {trace.get('trace_id')}  "
             f"{trace.get('span_count')} spans  "
             f"{trace.get('duration_s')}s  "
             f"nodes={','.join(n[:8] for n in trace.get('node_ids', []))}"]

    def walk(node, depth):
        took = "open" if node.get("end") is None \
            else f"{_duration_s(node) * 1000:.2f} ms"
        where = (node.get("worker_id") or "driver")[:8]
        err = "  ERROR" if node.get("error") else ""
        lines.append(f"{'  ' * depth}{node.get('name')}  "
                     f"[{took} @ {where}]{err}")
        for c in node.get("children", ()):
            walk(c, depth + 1)

    for r in trace.get("roots", ()):
        walk(r, 1)
    crit = trace.get("critical_path") or ()
    if crit:
        lines.append("critical path: " + " -> ".join(
            f"{s['name']} ({s['duration_s'] * 1000:.2f} ms)"
            for s in crit))
    return "\n".join(lines)


def export_chrome_trace(filename: Optional[str] = None,
                        trace_id: Optional[str] = None) -> List[dict]:
    """Spans + task timeline as ONE Chrome-trace JSON with a shared
    layout — **rows (pid) are nodes, threads (tid) are workers**, the
    same convention as `ray_tpu timeline`, so a serve request's proxy,
    replica, and nested-task spans line up under the workers that ran
    them (reference: `ray timeline` merged with span events)."""
    import json

    from . import state as state_api

    events = state_api.timeline()
    for s in get_spans(trace_id):
        if s.get("start") is None or s.get("end") is None:
            continue
        events.append({
            "cat": "span", "name": s.get("name") or "?", "ph": "X",
            "ts": s["start"] * 1e6, "dur": (s["end"] - s["start"]) * 1e6,
            "pid": (s.get("node_id") or "ray_tpu")[:8],
            "tid": (s.get("worker_id") or "driver")[:8],
            "args": {k: v for k, v in s.items()
                     if k in ("trace_id", "span_id", "parent_span_id",
                              "attributes", "error")},
        })
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events
