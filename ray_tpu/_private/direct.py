"""Direct worker<->worker call plane: the actor-call fast path.

Reference parity: the direct actor transport
(core_worker/transport/direct_actor_task_submitter.cc + task_receiver.cc)
— steady-state actor calls never route through a central process. The
caller submits straight to the callee worker and the GCS sees only
registration and failures.

Shape here: when a worker holds an actor handle whose callee is alive,
the head brokers a channel ONCE (CHANNEL_REQ -> CHANNEL_OPEN ->
CHANNEL_ADDR; same-node callers dial the callee's UNIX listener,
cross-node callers its TCP listener with the netcomm socket options),
and every subsequent ``actor.method.remote()`` ships an ACTOR_CALL frame
caller->callee on that channel, with the inline result returned
callee->caller as an ACTOR_RESULT on the same channel — both ends reuse
the PR 2 transport (ConnectionWriter coalescing, batch frames). The head
receives only oneway, batched accounting:

  * DIRECT_DONE — completion entries (result locations + the caller's
    residual local refcounts) so the object directory stays
    authoritative for refs that escape the caller;
  * REF_DELTAS — worker incref/decref coalesced into per-burst deltas;
  * WORKER_BLOCKED / WORKER_UNBLOCKED — the lease-release/recall signal
    the old blocking GET_LOCATIONS round trip used to carry implicitly.

Nested plain-task submission gets the cheaper half: the head forwards
results for worker-submitted tasks to the submitter (RESULT_FWD) as it
registers them, so the submitter's get() resolves locally with no pull
round trip.

Failure semantics: on callee death the channel EOF drains every
in-flight call through DIRECT_RECONCILE — the head routes each spec
through its normal retry machinery (ledger-bumped ``attempt``
accounting; requeue onto the restarted actor or a typed ActorDiedError).
A falsy ``direct_calls_enabled`` config routes everything through the
head path unchanged (zero additional work on the submit/complete paths —
guarded counter-based by tests/test_direct_calls.py).

Refcount transfer invariant: return ids of in-flight direct calls are
counted CALLER-LOCALLY (``_refs``); the residual transfers to the head
inside the DIRECT_DONE entry, enqueued on the caller's head pipe UNDER
``_cond`` in the same critical section that retires the local count — so
any later incref/decref for that id (which necessarily observed the
retired count) enqueues on the same FIFO pipe AFTER the registration it
depends on.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

from ..exceptions import ActorDiedError, GetTimeoutError
from ..util.profiling import PROFILE_METHOD
from . import fault
from . import object_store
from . import lockdep
from . import protocol as P
from . import racedebug
from . import refdebug
from . import serialization
from . import telemetry
from . import wiretap

logger = logging.getLogger(__name__)

# Counter of direct-plane operations in THIS process — the perf_smoke
# guard's counter-based proxy for "the disabled path did no direct-plane
# work" (same discipline as telemetry.instrument_ops / lockdep).
_ops = 0


def direct_ops() -> int:
    """Direct-plane operations performed so far (perf_smoke guard)."""
    return _ops


def _bump() -> None:
    global _ops
    _ops += 1


# Separate counter for the object-transfer fast path: pull_object bumps
# it past every disable gate, so the flag-off perf_smoke guard can
# window it without catching unrelated plane traffic (ref-delta
# batches, DIRECT_DONE receipts) that stays live while pulls are off.
_pull_ops = 0


def pull_ops() -> int:
    """Direct pull-plane operations so far (perf_smoke guard)."""
    return _pull_ops


class _Fallback:
    """This (caller, actor) pair is pinned to the head path. Permanent
    pins (actor dead, plane disabled, redial budget exhausted) never
    retry; transient pins (channel death, dial failure) may re-dial
    after a backoff cooldown — one TCP reset must not cost the pair its
    fast path for the process lifetime."""

    __slots__ = ("permanent", "attempts", "pinned_at")

    def __init__(self, permanent: bool = False, attempts: int = 0):
        self.permanent = permanent
        self.attempts = attempts
        self.pinned_at = time.monotonic()

    def redial_due(self) -> bool:
        if self.permanent:
            return False
        from .config import ray_config
        if self.attempts >= int(ray_config.direct_redial_max_attempts):
            return False
        backoff = float(ray_config.direct_redial_backoff_s) \
            * (2 ** max(0, self.attempts - 1))
        return time.monotonic() - self.pinned_at >= backoff


# Sentinel: permanently pinned to the head path — establishment was
# refused for a dead actor, or the plane is disabled.
_FALLBACK = _Fallback(permanent=True)


class _TransientEstablish(Exception):
    """The channel cannot be brokered YET (callee still constructing /
    restarting): the current call takes the head path, but the pair is
    NOT pinned to _FALLBACK — the next call retries establishment."""


class _RefusedEstablish(Exception):
    """The broker refused terminally (actor dead, plane off head-side):
    the pair pins to the head path permanently — re-dialing would only
    repeat the refusal round trip."""

# A "fwd"-pending local wait falls back to head GET_LOCATIONS after this
# long without a RESULT_FWD — the head's directory is authoritative for
# nested submissions, so a missed forward degrades to one round trip
# instead of a hang. Direct-pending ids never time out here: their
# resolution signal is the channel itself (result or EOF reconcile).
_FWD_RESYNC_S = 5.0

PENDING_DIRECT = "direct"
PENDING_FWD = "fwd"


class _DirectChannel:
    """Caller-side half of one brokered channel to one actor's worker."""

    __slots__ = ("plane", "actor_id", "conn", "writer", "alive",
                 "inflight", "queue", "pump_running", "_recv_thread",
                 "callee_wid", "seq_st", "node_hex")

    def __init__(self, plane: "DirectPlane", actor_id, conn,
                 callee_wid: Optional[str] = None,
                 node_hex: Optional[str] = None):
        self.plane = plane
        self.actor_id = actor_id
        self.conn = conn
        # Node identity of the callee (brokered with the listener
        # address): the object-transfer plane routes node-scoped pulls
        # over any live channel to a worker on the owning node.
        self.node_hex = node_hex
        # The (caller, actor) sequencing state, cached so the per-call
        # stamp/settle fast paths skip the registry lookup.
        with plane._cond:
            self.seq_st = plane._seq_state_locked(actor_id.binary())
        # Worker-id hex of the incarnation this channel dialed: the
        # reconcile payload carries it so the head can tell "requeued
        # onto the incarnation this EOF implicates" (prepaid retry)
        # from "requeued onto a later restart" (charges normally).
        self.callee_wid = callee_wid
        self.alive = True
        # task_id bytes -> spec, insertion-ordered (reconcile preserves
        # submission order). Guarded by plane._cond.
        self.inflight: "collections.OrderedDict[bytes, Any]" = \
            collections.OrderedDict()
        # Ordered not-yet-sent specs (ref args needing location
        # resolution park here; a single pump drains in order).
        self.queue: collections.deque = collections.deque()
        self.pump_running = False
        from .netcomm import ConnectionWriter
        self.writer = ConnectionWriter(
            conn, name=f"direct-w-{actor_id.hex()[:8]}")
        self._recv_thread = threading.Thread(
            target=self._recv_loop, daemon=True,
            name=f"direct-recv-{actor_id.hex()[:8]}")
        self._recv_thread.start()

    def _recv_loop(self):
        while True:
            try:
                data = self.conn.recv_bytes()
            except (EOFError, OSError):
                break
            try:
                self.plane._on_channel_messages(self, P.load_messages(data))
            except Exception:
                logger.exception("direct channel handler failed")
        self.plane._on_channel_down(self)

    def close(self):
        try:
            self.writer.close(flush_timeout=0.5)
        except Exception:  # lint: broad-except-ok teardown of an already-dead channel; nothing to report
            pass
        try:
            self.conn.close()
        except OSError:
            pass


class _ServeConn:
    """Callee-side half of one accepted direct connection: a writer for
    results plus the recv thread feeding the shared dispatch."""

    __slots__ = ("plane", "conn", "writer")

    def __init__(self, plane: "DirectPlane", conn):
        self.plane = plane
        self.conn = conn
        from .netcomm import ConnectionWriter
        self.writer = ConnectionWriter(conn, name="direct-serve-w")
        threading.Thread(target=self._recv_loop, daemon=True,
                         name="direct-serve-recv").start()

    def _recv_loop(self):
        while True:
            try:
                data = self.conn.recv_bytes()
            except (EOFError, OSError):
                break
            try:
                self.plane._on_channel_messages(self, P.load_messages(data))
            except Exception:
                logger.exception("direct serve handler failed")
        # Caller hung up: nothing to reconcile callee-side — in-flight
        # executions fall back to head accounting when their result
        # send fails (see send_result).
        try:
            self.writer.close(flush_timeout=0.0)
        except Exception:  # lint: broad-except-ok caller hung up mid-teardown; writer/conn close is best-effort
            pass
        try:
            self.conn.close()
        except OSError:
            pass


class DirectPlane:
    """Per-worker direct-call state: caller channels, the callee
    listener, the local result cache, and the coalesced accounting
    buffers. One instance per worker process (Worker.direct)."""

    def __init__(self, worker):
        self._worker = worker
        self._wid = worker.config.worker_id.binary()
        from .config import ray_config
        self.enabled = bool(ray_config.direct_calls_enabled)
        self.forwarding = self.enabled and bool(
            ray_config.direct_result_forwarding)
        self._cache_cap = max(64, int(ray_config.direct_result_cache_size))
        # THE plane lock/condition: local results, pending markers,
        # local refcounts, channel inflight/queues, ref-delta buffer.
        self._cond = lockdep.condition("direct.state")
        # actor_id bytes -> _DirectChannel | _FALLBACK (under _cond).
        self._chans: Dict[bytes, Any] = {}
        # Serializes channel establishment per process (head round trip).
        # NEVER taken on the worker's recv loop: _establish blocks in
        # request() under it, and the REPLY that completes that request
        # is delivered by the same loop that handles CHANNEL_OPEN — a
        # shared lock would let an inbound channel open wedge the
        # whole control plane against an outbound dial.
        self._estab_lock = lockdep.lock("direct.establish")
        # Listener creation (callee side, CHANNEL_OPEN on the recv
        # loop) gets its own lock for exactly that reason.
        self._listen_lock = lockdep.lock("direct.listener")
        # oid bytes -> loc: resolved results, evictable FIFO (the head's
        # directory is authoritative once DIRECT_DONE/register landed).
        self._results: "collections.OrderedDict[bytes, Tuple]" = \
            collections.OrderedDict()
        # oid bytes -> PENDING_DIRECT | PENDING_FWD: ids a local wait
        # must NOT ask the head about (direct) / prefers not to (fwd).
        self._pending: Dict[bytes, str] = {}
        # oid bytes -> [waiter_count_cell, ...]: local waits register a
        # per-wait countdown so a bulk get() wakes ONCE when its last
        # id resolves instead of on every result frame (on one core,
        # spurious waiter wakes are pure GIL churn).
        self._waiters: Dict[bytes, List] = {}
        # oid bytes -> caller-local refcount of in-flight AND
        # resolved-but-unflushed direct return ids (transferred to the
        # head inside DIRECT_DONE entries at flush time).
        self._refs: Dict[bytes, int] = {}
        # Coalesced incref/decref deltas bound for the head.
        self._ref_buf: Dict[bytes, List] = {}
        # Retired-but-unflushed DIRECT_DONE completion entries: the
        # steady-state path sends the head NOTHING per call — entries
        # drain at the accounting barriers (size threshold, any other
        # outbound head traffic, task completion).
        self._done_buf: List[dict] = []
        self._done_flush_n = 1024
        self._ref_flush_n = 1024
        # -- cross-plane call sequencing (caller side). Per actor:
        #   next   dense per-(this caller, actor) sequence counter
        #   d / h  UNSETTLED seqs by plane: in flight on the channel
        #          ("d") vs owned by the head ("h": fallback/streaming/
        #          retry_exceptions submissions and reconcile-requeued
        #          calls); stamping happens AT routing, so there is no
        #          undecided state
        #   hi     settled seqs at/above the min-unsettled watermark
        #          (shipped to the head at the reconcile/re-dial
        #          chokepoints so a fresh callee incarnation's merge
        #          gate can resolve stale predecessor references)
        #   ts     tid bytes -> submit wallclock (telemetry only)
        # All guarded by _cond.
        self._seq: Dict[bytes, dict] = {}
        # Streaming generator calls riding the channel: tid bytes ->
        # {count, finished, error, abandoned, items, nested, cbs}
        # (caller-side mirror of the head's _gen_streams). Guarded by
        # _cond; waiters ride the plane condition.
        self._streams: Dict[bytes, dict] = {}
        # Staged SUBMITTED tuples (task_id, name, ts, callee_wid_hex)
        # for stamped calls, drained into event dicts by the worker's
        # telemetry flush. Guarded by _cond.
        self._sub_evts: List = []
        # task_id bytes of calls whose ref args this caller pinned —
        # kept OFF the spec: a dynamic attr would demote the full-spec
        # ACTOR_CALL pickle to the slow extra-dict reduce and ship a
        # meaningless flag to the callee. set.remove under the GIL
        # keeps the unpin exactly-once across the unwind paths.
        self._pinned: set = set()
        # oid bytes of IN-FLIGHT direct return ids that a head-bound
        # message referenced (nested in a task result, arg of a head
        # submit or put): the head now holds interest, so their
        # eventual retirement must flush instead of parking — an idle
        # worker has no later barrier. Guarded by _cond.
        self._escaped: set = set()
        # Direct-path counters, pushed into the metric registry in
        # batches at accounting flushes (a per-call Metric.inc would
        # tax the very hot path this plane strips).
        self._n_calls = 0
        self._n_results = 0
        # Callee listener state (created lazily on CHANNEL_OPEN).
        self._listener_info: Optional[dict] = None
        self._listeners: List = []
        # -- direct object transfer plane (PULL_DIRECT / OBJ_CHUNK /
        # OBJ_EOF). Pull client state rides its OWN small lock, never
        # _cond: the chunk handler memcpys megabytes per frame on the
        # channel recv thread and must not hold THE plane lock while
        # it does. rid (int) -> pull state dict.
        self._pull_lock = lockdep.lock("direct.pulls")
        self._pulls: Dict[int, dict] = {}
        self._pull_seq = 0
        # In-flight pulls by object id: a second pull of the SAME
        # object from this process (shuffle prefetch racing a reducer
        # finish) must piggyback on the first, not double-reserve the
        # id in the store. oid bytes -> Event set when the winner ends.
        self._inflight_pulls: Dict[bytes, threading.Event] = {}
        # Callee-side admission: concurrently served pulls (guarded by
        # _pull_lock); excess requests refuse typed and the caller
        # falls back to the daemon path.
        self._serving_pulls = 0
        # Caller-side per-peer-node link gates (shuffle_link_inflight):
        # node_hex -> BoundedSemaphore, created lazily under _pull_lock.
        self._link_sems: Dict[str, threading.BoundedSemaphore] = {}
        # Lazy transfer thread pool — bulk pulls never queue behind a
        # long-running actor method on the actor executor (or vice
        # versa).
        self._xfer_exec = None

    # ------------------------------------------------------------------
    # refcounting: local-table interception + per-burst delta coalescing
    # ------------------------------------------------------------------
    def ref_delta(self, object_id, delta: int) -> None:
        """Adjust one ref: direct return ids still counted locally
        absorb the delta in place; everything else merges into the
        per-burst buffer shipped as one REF_DELTAS frame at the next
        accounting barrier (or on overflow)."""
        _bump()
        ob = object_id.binary()
        overflow = False
        with self._cond:
            if ob in self._refs:
                self._refs[ob] += delta
                if refdebug.enabled:
                    refdebug.absorb("direct.ref_delta", object_id, delta)
                return
            ent = self._ref_buf.get(ob)
            if ent is None:
                self._ref_buf[ob] = [object_id, delta]
            else:
                ent[1] += delta
            if refdebug.enabled:
                refdebug.park("direct.ref_delta", object_id, delta)
            overflow = len(self._ref_buf) >= self._ref_flush_n
        if overflow:
            self.flush_accounting()

    def note_escaped(self, nested_lists) -> None:
        """A head-bound message (task completion's nested result ids,
        a worker submit's args, a put) references these ids: any that
        are still IN-FLIGHT direct calls must flush at retirement —
        the head-side waiter created by that message has no other way
        to learn the result on an otherwise idle worker."""
        if not nested_lists or not any(nested_lists):
            return
        with self._cond:
            marked = [] if refdebug.enabled else None
            for ids in nested_lists:
                for nid in ids or ():
                    ob = nid.binary() if hasattr(nid, "binary") else nid
                    # In flight (pending) OR retired-but-unflushed
                    # (residual still local in _refs): either way the
                    # head's interest means the completion entry must
                    # neither park indefinitely nor be elided.
                    if (self._pending.get(ob) == PENDING_DIRECT
                            or ob in self._refs):
                        self._escaped.add(ob)
                        if marked is not None:
                            marked.append(ob)
            if refdebug.enabled and marked:
                refdebug.escape(marked)

    def note_spec_escapes(self, spec) -> None:
        """Head-submitted spec: its ref args (and their nested ids)
        escape to the head — see note_escaped."""
        ids = None
        for a in list(spec.args) + list(spec.kwargs.values()):
            if a.object_id is not None or a.nested_ids:
                if ids is None:
                    ids = []
                if a.object_id is not None:
                    ids.append(a.object_id)
                ids.extend(a.nested_ids)
        if ids:
            self.note_escaped([ids])

    def flush_accounting(self) -> None:
        """THE ordering barrier: drain buffered completion entries and
        ref deltas onto the head pipe BEFORE the caller enqueues
        anything that could reference them (a nested submit pinning a
        direct result, a put nesting one, a TASK_DONE unpinning borrow
        increfs). Sends happen UNDER _cond so nothing this worker later
        enqueues can overtake the accounting it depends on."""
        # Racy fast path: both buffers only become non-empty under
        # _cond; if another thread's entries are in flight, our own
        # messages carry no dependency on them.
        if (not self._done_buf and not self._ref_buf  # lint: guarded-by-ok documented racy fast path: buffers fill under _cond; our own frames carry no dependency on another thread's in-flight entries
                and not (self._n_calls or self._n_results)):
            return
        _bump()
        with self._cond:
            self._flush_accounting_locked()

    def _flush_accounting_locked(self) -> None:
        """Caller holds self._cond."""
        settled = [] if refdebug.enabled else None
        if self._done_buf:
            entries, self._done_buf = self._done_buf, []
            ship = []
            for ent in entries:
                obs = [oid.binary() for oid in ent["oids"]]
                if settled is not None:
                    settled.extend(ob for ob in obs if ob in self._refs)
                deltas = [self._refs.pop(ob, 0) for ob in obs]
                # Escaped ids (nested into a head-bound message while
                # locally owned) can net a ZERO local residual — the
                # handle incref parked in _ref_buf pre-submit while the
                # drop hit _refs — even though the head holds a real
                # nested pin and a waiter. They must always ship.
                escaped = any(ob in self._escaped for ob in obs)
                for ob in obs:
                    self._escaped.discard(ob)
                # Dead-entry elision: every ref already dropped AND no
                # backing to reclaim (inline/error locs only) means NO
                # party can ever reference these ids — any escape path
                # (nested ids, task args, puts) pins them BEFORE its
                # own message passes this barrier, which would have
                # kept the residual positive (or marked them escaped).
                # The head never needs to hear about them; steady-state
                # call-and-drop bursts cost it zero registrations
                # (submission-side task events ride the caller's OWN
                # event buffer instead — see _mark_routed_locked).
                if (not escaped
                        and "gen" not in ent
                        and all(d <= 0 for d in deltas)
                        and not any(ln for ln in ent["nested"])
                        and all(l[0] != P.LOC_SHM for l in ent["locs"])):
                    continue
                ent["deltas"] = deltas
                ship.append(ent)
            if ship:
                try:
                    self._worker.send_lazy(P.DIRECT_DONE,
                                           {"entries": ship})
                except Exception:  # lint: broad-except-ok head pipe dead: the worker process is exiting, accounting dies with it
                    pass
        if self._ref_buf:
            buf, self._ref_buf = self._ref_buf, {}
            if settled is not None:
                settled.extend(buf.keys())
            items = [(oid, d) for oid, d in buf.values() if d]
            if items:
                try:
                    self._worker.send_lazy(P.REF_DELTAS, {"deltas": items})
                except Exception:  # lint: broad-except-ok head pipe dead: the worker process is exiting, deltas die with it
                    pass
        # Counters reset unconditionally: they also feed the
        # empty-buffer fast path in flush_accounting — leaving them
        # nonzero with telemetry off would defeat it forever after the
        # first direct call.
        n_calls, self._n_calls = self._n_calls, 0
        n_results, self._n_results = self._n_results, 0
        if refdebug.enabled:
            refdebug.barrier(settled or [])
        if telemetry.enabled:
            if n_calls:
                telemetry.record_direct_calls(n_calls)
            if n_results:
                telemetry.record_direct_results(n_results)

    # ------------------------------------------------------------------
    # cross-plane call sequencing (caller side)
    #
    # Every actor call this worker submits is stamped with a dense
    # per-(caller, actor) sequence number BEFORE routing, plus the list
    # of its still-unsettled OTHER-plane predecessors — the callee's
    # merge gate (worker_proc.SequenceGate) holds out-of-order arrivals
    # until those predecessors execute there or the head settles them.
    # Same-plane predecessors need no list: the channel is FIFO and the
    # head's per-actor queue dispatches one caller's calls in seq order.
    # ------------------------------------------------------------------
    def _seq_state_locked(self, ab: bytes) -> dict:
        st = self._seq.get(ab)
        if st is None:
            # next: dense counter. d/h/p: UNSETTLED seqs by plane
            # (direct / head-owned / pending-routing). w: contiguous
            # settled watermark (every seq < w settled); hi: settled
            # seqs >= w (sparse holes while an older call is in
            # flight). All hot-path transitions are O(1) amortized —
            # the per-call scans must never touch the in-flight window
            # (burst cost would go quadratic).
            st = self._seq[ab] = {"next": 0, "d": set(), "h": set(),
                                  "w": 0, "hi": set()}
        return st

    def _mark_routed_locked(self, spec, plane: str, chan=None) -> None:
        """Assign the call's sequence slot on FIRST routing (sequence
        order is defined by registration order under _cond — no second
        lock round trip per call) and snapshot its cross-plane
        predecessors. `plane` is "d" or "h". The steady-state direct
        path scans only the head-owned + pending sets (near-empty),
        never the in-flight direct window."""
        st = self._seq_state_locked(spec.actor_id.binary())
        seq = spec.caller_seq
        if seq < 0:
            seq = st["next"]
            st["next"] = seq + 1
            spec.caller_seq = seq
            spec.caller_id = self._wid
            if telemetry.enabled:
                # SUBMITTED staged as a bare tuple under the lock we
                # already hold; the telemetry flush ships the batch
                # raw and the HEAD converts to event dicts at ingest
                # (riding existing frames — zero per-call head
                # messages), closing the direct-call state-API
                # submission gap.
                self._sub_evts.append(
                    (spec.task_id.binary(), spec.name, time.time(),
                     getattr(chan, "callee_wid", None)))
        else:
            # Rerouted (channel send unwound -> head path): leave the
            # old plane set.
            st["d"].discard(seq)
            st["h"].discard(seq)
        if plane == "d":
            other = st["h"] if st["h"] else ()
            st["d"].add(seq)
        else:
            other = st["d"] if st["d"] else ()
            st["h"].add(seq)
        spec.seq_preds = tuple(sorted(
            s for s in other if s < seq)) if other else ()

    def mark_head_routed(self, spec) -> None:
        """The call takes the head path (fallback, streaming before a
        channel exists, retry_exceptions, unwound channel send): stamp
        it (first routing) and snapshot the in-flight channel calls as
        its predecessors."""
        _bump()
        with self._cond:
            self._mark_routed_locked(spec, "h")

    def _settle_seq_locked(self, ab: bytes, seq: int) -> None:
        """This call is terminally settled caller-side (result or error
        delivered locally, or ownership confirmed done by the head): it
        can never again be anyone's missing predecessor on a FUTURE
        incarnation, so it joins the settled set shipped to the head at
        the reconcile/re-dial chokepoints. Contiguous settlement (the
        steady state) compacts into the watermark, amortized O(1)."""
        if seq < 0:
            return
        st = self._seq.get(ab)
        if st is None:
            return
        st["d"].discard(seq)
        st["h"].discard(seq)
        if seq == st["w"] and not st["hi"]:
            st["w"] = seq + 1  # contiguous settlement fast path
            return
        if seq < st["w"] or seq in st["hi"]:
            return
        st["hi"].add(seq)
        hi = st["hi"]
        while st["w"] in hi:
            hi.discard(st["w"])
            st["w"] += 1

    def _seq_snapshot_locked(self, ab: bytes):
        """(settled_below, settled_set) for the head's settlement store
        (caller holds _cond): every seq < settled_below is settled;
        settled_set are the settled ones above it (holes exist while an
        older call is still unsettled)."""
        st = self._seq.get(ab)
        if st is None:
            return None
        return st["w"], sorted(st["hi"])

    def drain_submitted(self) -> List:
        """Staged SUBMITTED tuples (task_id_bytes, name, ts,
        callee_wid), shipped raw inside the TASK_EVENTS frame — the
        HEAD converts to event dicts at ingest, so the hot path and
        the worker-side drain pay tuple appends and one pickle each,
        nothing more."""
        if not self._sub_evts:  # lint: guarded-by-ok racy emptiness fast path: a miss just defers the drain to the next TASK_EVENTS tick
            return []
        with self._cond:
            staged, self._sub_evts = self._sub_evts, []
        return staged

    def on_seq_settled(self, payload: dict) -> None:
        """SEQ_SETTLED from the head. Two independent, idempotent
        halves: as a CALLER, prune the listed slots from the unsettled
        map (they were settled head-side without this worker seeing a
        result frame — typed reconcile errors, dead-actor failures); as
        a CALLEE, release merge-gate holds waiting on them."""
        ab = payload.get("actor_id")
        seqs = payload.get("seqs") or ()
        if ab is not None:
            with self._cond:
                for s in seqs:
                    self._settle_seq_locked(ab, s)
        caller = payload.get("caller_id")
        if caller is not None:
            self._worker.seq_gate_settled(caller, seqs,
                                          all_=bool(payload.get("all")))

    # ------------------------------------------------------------------
    # local result cache / pending markers
    # ------------------------------------------------------------------
    def _cache_put_locked(self, ob: bytes, loc) -> None:
        if racedebug.enabled:
            racedebug.access(self, "_results", write=True)
        res = self._results
        res[ob] = loc
        res.move_to_end(ob)
        while len(res) > self._cache_cap:
            # Evict oldest FLUSHED entry only: an id still carrying a
            # local refcount is unknown to the head — its cached loc is
            # the ONLY copy until the accounting drains.
            for old in res:
                if old not in self._refs:
                    del res[old]
                    break
            else:
                break

    def note_nested_submission(self, spec) -> None:
        """Mark a head-routed worker submission's return ids as
        forward-pending: the head pushes their locations back
        (RESULT_FWD) as it registers them, so get() resolves locally."""
        if not self.forwarding:
            return
        _bump()
        rids = getattr(spec, "return_ids", None)
        if not rids:
            return
        with self._cond:
            for rid in rids:
                self._pending[rid.binary()] = PENDING_FWD

    def _resolve_pending_locked(self, ob: bytes) -> bool:
        """Retire one pending id; True when some waiter's LAST missing
        id just resolved (only then is a wake worth its GIL cost)."""
        self._pending.pop(ob, None)
        cells = self._waiters.pop(ob, None)
        wake = False
        if cells:
            for cell in cells:
                cell[0] -= 1
                if cell[0] <= 0:
                    wake = True
        return wake

    def on_result_fwd(self, payload: dict) -> None:
        """RESULT_FWD from the head: cache forwarded locations; a None
        loc demotes the id to the head-request path (lost/freed)."""
        wake = False
        with self._cond:
            for oid, loc in payload.get("entries", ()):
                ob = oid.binary()
                if self._resolve_pending_locked(ob):
                    wake = True
                if loc is not None:
                    self._cache_put_locked(ob, loc)
            if wake:
                self._cond.notify_all()

    # ------------------------------------------------------------------
    # get(): local-first location resolution
    # ------------------------------------------------------------------
    def get_locations(self, object_ids, timeout=None,
                      notify_blocked: bool = True) -> List:
        """Resolve locations local-first: direct results and forwarded
        nested results come out of the local cache (waiting on the
        channel/forward signal when still in flight); everything else
        falls through to one head GET_LOCATIONS request. While a local
        wait actually blocks, the head is told via oneway
        WORKER_BLOCKED/WORKER_UNBLOCKED so lease release and
        queued-task recall behave exactly like the old blocking
        round trip. `notify_blocked=False` for waits OFF the
        task-execution path (the pump thread): the executor is still
        running at full speed, and releasing the lease would let the
        scheduler oversubscribe the worker's CPU slot."""
        _bump()
        w = self._worker
        deadline = None if timeout is None else time.monotonic() + timeout
        out: Dict[bytes, Tuple] = {}
        need_head: List = []
        blocked = False
        wait_t0 = None
        try:
            with self._cond:
                # Incremental resolution: each wake rescans only the
                # still-unresolved tail, not the whole id list (a burst
                # of N results would otherwise cost O(N^2) lookups).
                pend: List[Tuple[Any, bytes]] = []
                for oid in object_ids:
                    ob = oid.binary()
                    loc = self._results.get(ob)
                    if loc is not None:
                        out[ob] = loc
                    elif ob in self._pending:
                        pend.append((oid, ob))
                    else:
                        need_head.append(oid)
                if pend:
                    # Countdown cell: resolution paths wake this wait
                    # only when its LAST missing id lands (bulk gets
                    # wake once, not once per result frame).
                    cell = [len(pend)]
                    for _oid, ob in pend:
                        self._waiters.setdefault(ob, []).append(cell)
                while pend:
                    now = time.monotonic()
                    if wait_t0 is None:
                        wait_t0 = now
                    elif now - wait_t0 > _FWD_RESYNC_S:
                        # Forward-pending ids the head already knows:
                        # stop trusting the push and ask (a missed
                        # forward must degrade, not hang). Direct ids
                        # stay — their signal is the channel itself.
                        # Demoted ids route to the head pull NOW:
                        # nothing will ever notify this wait for a
                        # missed forward, so sleeping another cond
                        # interval first would just pad the documented
                        # one-pull degrade by up to a second.
                        still = []
                        for oid, ob in pend:
                            if self._pending.get(ob) != PENDING_FWD:
                                still.append((oid, ob))
                                continue
                            self._resolve_pending_locked(ob)
                            loc = self._results.get(ob)
                            if loc is not None:
                                out[ob] = loc
                            else:
                                need_head.append(oid)
                        pend = still
                        if not pend:
                            break
                    if deadline is not None and now >= deadline:
                        raise GetTimeoutError(
                            "Get timed out waiting for direct-call "
                            "results")
                    if not blocked and notify_blocked:
                        blocked = True
                        try:
                            w.send_lazy(P.WORKER_BLOCKED, {})
                        except Exception:  # lint: broad-except-ok blocked-notify is advisory; a dead head pipe fails the wait itself
                            pass
                    remaining = None if deadline is None \
                        else deadline - now
                    self._cond.wait(
                        timeout=min(remaining, 1.0)
                        if remaining is not None else 1.0)
                    still: List[Tuple[Any, bytes]] = []
                    for oid, ob in pend:
                        loc = self._results.get(ob)
                        if loc is not None:
                            out[ob] = loc
                        elif ob in self._pending:
                            still.append((oid, ob))
                        else:
                            need_head.append(oid)
                    pend = still
        finally:
            if blocked:
                try:
                    w.send_lazy(P.WORKER_UNBLOCKED, {})
                except Exception:  # lint: broad-except-ok unblock-notify is advisory, same as the blocked-notify above
                    pass
        if need_head:
            remaining = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            locs = w.request(P.GET_LOCATIONS, {
                "object_ids": need_head,
                "timeout": remaining if timeout is not None else None})
            for oid, loc in zip(need_head, locs):
                out[oid.binary()] = loc
        return [out[oid.binary()] for oid in object_ids]

    # ------------------------------------------------------------------
    # caller side: submit
    # ------------------------------------------------------------------
    def submit_actor_call(self, spec) -> bool:
        """Ship one actor method call on the direct channel. False =>
        the caller must take the head path (no channel, channel dead,
        plane fell back for this actor)."""
        if spec.retry_exceptions:
            # User-exception retries are a HEAD decision (TASK_DONE's
            # resubmit-on-error branch): on the channel the callee's
            # error blob would retire terminally at the caller with
            # zero retries — flag-on/flag-off behavior must not
            # diverge, so these rare opt-in calls stay head-routed.
            return False
        _bump()
        chan = self._channel_for(spec.actor_id)
        if chan is None:
            return False
        try:
            return self._submit_on_channel(chan, spec)
        except Exception:
            logger.debug("direct submit failed; falling back",
                         exc_info=True)
            return False

    def _channel_for(self, actor_id) -> Optional[_DirectChannel]:
        ab = actor_id.binary()
        chan = self._chans.get(ab)  # lint: guarded-by-ok double-checked fast path: GIL-atomic get, re-read below before any mutation
        if isinstance(chan, _Fallback):
            # Transient pins (channel death, dial failure) re-dial once
            # the backoff cooldown elapses, bounded by
            # direct_redial_max_attempts; permanent pins never do.
            if not chan.redial_due():
                return None
        elif chan is not None and chan.alive:
            return chan
        with self._estab_lock:
            chan = self._chans.get(ab)  # lint: guarded-by-ok _estab_lock serializes dialers; _chans INSERTS happen under it too, only retirement needs _cond
            prior = None
            if isinstance(chan, _Fallback):
                if not chan.redial_due():
                    return None
                prior = chan
            elif chan is not None and chan.alive:
                return chan
            try:
                chan = self._establish(actor_id)
                if prior is not None and telemetry.enabled:
                    telemetry.record_direct_fallback("redial")
            except _TransientEstablish as e:
                # Callee pending/restarting: head path for THIS call,
                # but the pair stays unpinned so the next call retries
                # the channel once the actor is up. A first burst
                # racing the actor's construction must not cost the
                # pair its direct plane forever.
                logger.debug("direct channel to actor %s not ready: "
                             "%r (head path, will retry)",
                             actor_id.hex()[:8], e)
                if telemetry.enabled:
                    telemetry.record_direct_fallback("pending")
                with self._cond:
                    self._chans.pop(ab, None)
                return None
            except _RefusedEstablish as e:
                logger.debug("direct channel to actor %s refused: %r "
                             "(head path, pinned)", actor_id.hex()[:8], e)
                if telemetry.enabled:
                    telemetry.record_direct_fallback("refused")
                with self._cond:
                    self._chans[ab] = _FALLBACK
                return None
            except Exception as e:
                logger.debug("direct channel to actor %s unavailable: "
                             "%r (head path)", actor_id.hex()[:8], e)
                if telemetry.enabled:
                    telemetry.record_direct_fallback("connect")
                chan = None
            with self._cond:
                if chan is not None:
                    self._chans[ab] = chan
                else:
                    # A dead-actor broker refusal pins permanently; a
                    # connect/dial failure is re-dialable after backoff.
                    self._chans[ab] = _Fallback(
                        attempts=(prior.attempts + 1) if prior is not None
                        else 1)
            return chan

    def _establish(self, actor_id) -> _DirectChannel:
        """One-time broker round trip + dial (reference: the actor
        handle resolving the callee's RPC address from the GCS once,
        then submitting directly)."""
        from .config import ray_config
        # Ship the caller's settlement snapshot with the dial: a fresh
        # callee incarnation's merge gate may hold arrivals on stale
        # predecessor references (calls settled on a previous
        # incarnation that the head never heard about — elided
        # accounting); the head folds this into its settlement store so
        # the gate's resync can release them.
        with self._cond:
            snap = self._seq_snapshot_locked(actor_id.binary())
        req = {"actor_id": actor_id}
        if snap is not None:
            req["settled_below"], req["settled_set"] = snap
        rep = self._worker.request(P.CHANNEL_REQ, req)
        if not isinstance(rep, dict) or not rep.get("ok"):
            if isinstance(rep, dict) and rep.get("transient"):
                raise _TransientEstablish(rep.get("reason") or "pending")
            raise _RefusedEstablish(
                f"channel broker refused: "
                f"{rep.get('reason') if isinstance(rep, dict) else rep}")
        if fault.enabled:
            fault.fire("direct.connect", actor=actor_id.hex()[:8])
        key = bytes.fromhex(rep["key"])
        my_node = self._worker.config.node_id_hex
        dial_budget = float(ray_config.direct_channel_timeout_s)
        conn = None
        if rep.get("unix") and (not rep.get("callee_node")
                                or rep["callee_node"] == my_node
                                or my_node is None):
            conn = self._dial(rep["unix"], "AF_UNIX", key, dial_budget)
        elif rep.get("tcp"):
            host, port = rep["tcp"]
            conn = self._dial((host, int(port)), "AF_INET", key,
                              dial_budget)
            from .netcomm import tune_control_socket
            tune_control_socket(conn.fileno())
        else:
            raise RuntimeError("broker reply carries no dialable address")
        return _DirectChannel(self, actor_id, conn,
                              callee_wid=rep.get("callee_worker"),
                              node_hex=rep.get("callee_node"))

    @staticmethod
    def _dial(address, family: str, key: bytes, timeout: float):
        """Bounded channel dial. `multiprocessing.connection.Client`
        has no timeout, and _establish runs under _estab_lock — a
        wedged callee (SIGSTOPped mid-accept) would otherwise hang this
        dial forever AND every other channel establishment in the
        worker behind the lock, with no fallback to the head path. The
        watchdog thread is abandoned on timeout (dials are once per
        (caller, actor) pair; a late connect is closed by GC and the
        callee's listener sees plain EOF)."""
        from multiprocessing.connection import Client
        box: List = []
        gave_up = []
        box_lock = threading.Lock()

        def _run():
            try:
                c = Client(address, family=family, authkey=key)
            except BaseException as e:  # lint: broad-except-ok shipped to the dialing thread below verbatim
                box.append(("err", e))
                return
            # Handoff under the lock: either the dialer takes the
            # connection from box, or it already gave up and this
            # thread owns the close — no window where neither side
            # closes a late connect.
            with box_lock:
                if not gave_up:
                    box.append(("ok", c))
                    return
            try:
                c.close()
            except OSError:
                pass

        t = threading.Thread(target=_run, daemon=True,
                             name="direct-dial")
        t.start()
        t.join(timeout)
        with box_lock:
            if not box:
                gave_up.append(True)
                raise TimeoutError(
                    f"direct channel dial to {address!r} timed out "
                    f"after {timeout}s")
            kind, val = box[0]
        if kind == "err":
            raise val
        return val

    def _pin_args(self, spec, delta: int) -> None:
        for a in list(spec.args) + list(spec.kwargs.values()):
            if a.kind == "ref" and a.object_id is not None:
                self.ref_delta(a.object_id, delta)
            for nid in a.nested_ids:
                self.ref_delta(nid, delta)

    def _unpin_once(self, spec) -> None:
        """Release the caller-side arg pin exactly once (set.remove is
        atomic under the GIL: one unwind path wins, the rest no-op)."""
        try:
            self._pinned.remove(spec.task_id.binary())
        except KeyError:
            return
        self._pin_args(spec, -1)

    def _fill_known_locations(self, spec) -> bool:
        """Fill ref-arg locations from the local cache; True when every
        ref arg now carries a location (inline fast path)."""
        ok = True
        with self._cond:
            for a in list(spec.args) + list(spec.kwargs.values()):
                if a.kind != "ref" or a.object_id is None:
                    continue
                if a.location is None:
                    a.location = self._results.get(a.object_id.binary())
                if a.location is None:
                    ok = False
        return ok

    def _submit_on_channel(self, chan: _DirectChannel, spec) -> bool:
        has_refs = any(a.kind == "ref" or a.nested_ids
                       for a in spec.args) \
            or (spec.kwargs and any(a.kind == "ref" or a.nested_ids
                                    for a in spec.kwargs.values()))
        tid = spec.task_id.binary()
        if has_refs:
            # Pin ref args for the call's lifetime (the head pins on
            # its path; here the caller is the pinning owner). The pin
            # must be head-VISIBLE before the call ships: the channel
            # is not a head message, so a buffered +1 would cancel
            # against the retire -1 and be elided — the head would
            # never hear the pin, and a handle drop racing the callee's
            # borrow incref (different pipe, no ordering) could free
            # the arg under a live borrow. One oneway frame per
            # ref-arg call; the no-arg hot path pays nothing.
            self._pin_args(spec, 1)
            self._pinned.add(tid)
            self.flush_accounting()
            resolved = self._fill_known_locations(spec)
        else:
            resolved = True
        start_pump = False
        send_now = False
        with self._cond:
            if not chan.alive:
                dead = True
            else:
                dead = False
                # Stamp + plane fixed at registration: the sequence
                # slot, the cross-plane predecessor snapshot, and the
                # channel-FIFO send order are all decided under ONE
                # lock hold. Inlined steady-state fast path (fresh
                # stamp, no cross-plane predecessors).
                sq = chan.seq_st
                if spec.caller_seq < 0 and not sq["h"]:
                    seq = sq["next"]
                    sq["next"] = seq + 1
                    spec.caller_seq = seq
                    spec.caller_id = self._wid
                    spec.seq_preds = ()
                    sq["d"].add(seq)
                    if telemetry.enabled:
                        self._sub_evts.append(
                            (spec.task_id.binary(), spec.name,
                             time.time(), chan.callee_wid))
                else:
                    self._mark_routed_locked(spec, "d", chan)
                if spec.streaming:
                    # Items stream back as GEN_ITEM frames on this
                    # channel; the caller-side stream state mirrors the
                    # head's _gen_streams (count/finished/error).
                    self._streams[tid] = {
                        "count": 0, "finished": False, "error": None,
                        "abandoned": False, "items": [], "cbs": [],
                        "actor": spec.actor_id}
                for rid in spec.return_ids:
                    self._refs[rid.binary()] = 1
                    self._pending[rid.binary()] = PENDING_DIRECT
                    if refdebug.enabled:
                        refdebug.borrow("direct.submit", rid)
                chan.inflight[tid] = spec
                self._n_calls += 1
                # pump_running covers the pop-then-send window: the
                # pump pops the last queued spec under this lock but
                # sends it after releasing, so an empty queue alone
                # does not mean the writer saw every prior call yet —
                # bypassing here would let this call overtake it.
                if chan.queue or not resolved or chan.pump_running:
                    chan.queue.append(spec)
                    if not chan.pump_running:
                        chan.pump_running = True
                        start_pump = True
                else:
                    send_now = True
        if dead:
            self._unpin_once(spec)
            return False
        if start_pump:
            threading.Thread(target=self._pump, args=(chan,), daemon=True,
                             name="direct-pump").start()
        if send_now:
            try:
                self._send_call(chan, spec)
            except Exception:
                # Returning False resubmits via the head path, so the
                # registration above MUST be unwound or the spec is
                # owned twice (head submission now + channel reconcile
                # at EOF → duplicate execution) and the orphaned local
                # refcount absorbs every future decref for the id. The
                # inflight pop decides ownership: losing it means a
                # concurrent channel-down reconcile already routed the
                # spec to the head — report success so the caller does
                # NOT submit it again.
                with self._cond:
                    owned = chan.inflight.pop(tid, None) is not None
                    if owned:
                        self._n_calls -= 1
                        self._streams.pop(tid, None)
                        for rid in spec.return_ids:
                            rb = rid.binary()
                            # Brand-new ids: no other thread has seen
                            # them yet, so the plain pops are exact.
                            self._refs.pop(rb, None)
                            self._resolve_pending_locked(rb)
                if not owned:
                    return True
                self._unpin_once(spec)
                logger.debug("direct send failed; falling back",
                             exc_info=True)
                return False
        return True

    def _send_call(self, chan: _DirectChannel, spec) -> None:
        if fault.enabled:
            fault.fire("direct.call", task=spec.name)
        if not spec.args and not spec.kwargs and not spec.streaming:
            # Compact wire form for the no-arg fast path: raw id bytes
            # in a tuple pickle ~2x faster than the spec's dataclass
            # reduce (the callee rebuilds an equivalent spec). The
            # sequencing triple and the trace context ride as tail
            # slots — traced calls keep the compact form instead of
            # silently demoting to the full-spec pickle (the slot is
            # None on the untraced steady state: ~1 byte).
            payload = {"c": (
                spec.task_id.binary(), spec.actor_id.binary(),
                spec.method_name, spec.name,
                [r.binary() for r in spec.return_ids],
                spec.num_returns, spec.fn_id,
                spec.caller_id, spec.caller_seq, spec.seq_preds,
                spec.trace_ctx)}
            if wiretap.enabled:
                wiretap.frame("direct", "caller", id(chan), "send",
                              P.ACTOR_CALL, payload)
            chan.writer.send_message(P.ACTOR_CALL, payload)
            return
        payload = {"spec": spec}
        if wiretap.enabled:
            wiretap.frame("direct", "caller", id(chan), "send",
                          P.ACTOR_CALL, payload)
        chan.writer.send_message(P.ACTOR_CALL, payload)

    def _pump(self, chan: _DirectChannel) -> None:
        """Ordered drain of calls whose ref args needed location
        resolution: one pump per channel, head-of-line blocking so
        per-caller submission order holds exactly."""
        while True:
            with self._cond:
                if not chan.queue or not chan.alive:
                    chan.pump_running = False
                    return
                spec = chan.queue[0]
            try:
                need = [a.object_id
                        for a in list(spec.args)
                        + list(spec.kwargs.values())
                        if a.kind == "ref" and a.object_id is not None
                        and a.location is None]
                if need:
                    locs = self.get_locations(need, notify_blocked=False)
                    by_id = {o.binary(): l for o, l in zip(need, locs)}
                    for a in list(spec.args) + list(spec.kwargs.values()):
                        if (a.kind == "ref" and a.object_id is not None
                                and a.location is None):
                            a.location = by_id.get(a.object_id.binary())
            except Exception:
                logger.debug("direct pump resolution failed for %s",
                             getattr(spec, "name", "?"), exc_info=True)
                # Channel-down reconcile owns the queued specs; if the
                # channel is alive but this spec is unresolvable, fail
                # it back through reconcile-like local error delivery.
                with self._cond:
                    if chan.queue and chan.queue[0] is spec:
                        chan.queue.popleft()
                    alive = chan.alive
                if alive:
                    self._fail_call_locally(chan, spec, RuntimeError(
                        "direct-call argument resolution failed"))
                continue
            with self._cond:
                if not chan.alive:
                    chan.pump_running = False
                    return
                if chan.queue and chan.queue[0] is spec:
                    chan.queue.popleft()
            try:
                self._send_call(chan, spec)
            except Exception:
                # A send failure is the channel dying under us (writer
                # EPIPE can beat the recv loop's EOF), NOT a property of
                # this spec: delivering a local error here would strip
                # the call of its reconcile retry/typed-ActorDiedError
                # semantics. The spec is still in chan.inflight — tear
                # the channel down and let the reconcile drain it (and
                # the rest of the queue) through the head's normal
                # retry machinery. Idempotent vs the recv loop's own
                # EOF handling.
                logger.debug("direct pump send failed for %s; "
                             "reconciling channel",
                             getattr(spec, "name", "?"), exc_info=True)
                with self._cond:
                    chan.pump_running = False
                self._on_channel_down(chan)
                return

    def _fail_call_locally(self, chan, spec, exc) -> None:
        blob = serialization.dumps(
            exc if isinstance(exc, BaseException) else RuntimeError(
                str(exc)))
        cbs = []
        with self._cond:
            chan.inflight.pop(spec.task_id.binary(), None)
            if spec.streaming:
                cbs = self._retire_stream_locked(spec, 0, blob)
            else:
                self._retire_locked(spec, None, blob, None)
            self._flush_accounting_locked()
            self._cond.notify_all()
        self._unpin_once(spec)
        for cb in cbs:
            try:
                cb()
            except Exception:  # lint: broad-except-ok user stream-done callback; failure delivery must complete
                logger.debug("stream done-callback raised", exc_info=True)

    # ------------------------------------------------------------------
    # caller side: results / reconcile
    # ------------------------------------------------------------------
    def _on_channel_messages(self, chan, msgs) -> None:
        """Burst entry for one received frame: ACTOR_RESULT runs are
        retired under ONE lock hold / ONE DIRECT_DONE accounting frame
        (the receive-side face of the writer's coalescing)."""
        if wiretap.enabled:
            wiretap.frames(
                "direct",
                "caller" if isinstance(chan, _DirectChannel) else "callee",
                id(chan), "recv", msgs)
        i, n = 0, len(msgs)
        while i < n:
            msg_type, payload = msgs[i]
            if msg_type == P.ACTOR_RESULT:
                j = i + 1
                while j < n and msgs[j][0] == P.ACTOR_RESULT:
                    j += 1
                self._on_actor_results(chan, [m[1] for m in msgs[i:j]])
                i = j
                continue
            if msg_type == P.ACTOR_CALL:
                j = i + 1
                while j < n and msgs[j][0] == P.ACTOR_CALL:
                    j += 1
                self._on_actor_calls(chan, [m[1] for m in msgs[i:j]])
                i = j
                continue
            if msg_type == P.GEN_ITEM:
                j = i + 1
                while j < n and msgs[j][0] == P.GEN_ITEM:
                    j += 1
                self._on_gen_items(chan, [m[1] for m in msgs[i:j]])
                i = j
                continue
            self._handle_direct_message(chan, msg_type, payload)
            i += 1

    def _handle_direct_message(self, chan, msg_type: str,
                               payload: dict) -> None:
        """Route one direct-channel message (both roles share this
        dispatcher: callee sees ACTOR_CALL, caller sees ACTOR_RESULT
        and streamed GEN_ITEM frames)."""
        if msg_type == P.ACTOR_CALL:
            self._on_actor_call(chan, payload)
        elif msg_type == P.ACTOR_RESULT:
            self._on_actor_results(chan, [payload])
        elif msg_type == P.GEN_ITEM:
            self._on_gen_items(chan, [payload])
        elif msg_type == P.SERVE_REQ:
            self._on_serve_req(chan, payload)
        elif msg_type == P.SERVE_BODY_FREE:
            self._on_serve_body_free(payload)
        elif msg_type == P.OBJ_CHUNK:
            self._on_obj_chunk(chan, payload)
        elif msg_type == P.OBJ_EOF:
            self._on_obj_eof(chan, payload)
        elif msg_type == P.PULL_DIRECT:
            self._on_pull_direct(chan, payload)
        elif msg_type == P.GEN_CANCEL:
            # Caller dropped its channel-stream generator mid-iteration:
            # stop the producing generator here (the head-routed path
            # cancels via CANCEL_TASK; this is the channel mirror). The
            # async-exc raise lands in the executing thread's `for item
            # in gen:` loop; already-finished tasks are a no-op.
            from .ids import TaskID
            self._worker._cancel(TaskID(payload["t"]))
        else:
            # Protocol skew between two workers: never silently drop.
            logger.warning("direct channel dropping unknown message "
                           "type %r (protocol skew?)", msg_type)

    def _retire_locked(self, spec, locs, error, nested) -> None:
        """Retire one call's return ids (caller holds self._cond): cache
        locations and park the completion entry in the accounting
        buffer. The local refcounts STAY in ``_refs`` — still absorbing
        incref/decref in place — until the buffer drains at an
        accounting barrier, where the residual deltas are popped into
        the DIRECT_DONE entry under the same lock."""
        if error is not None:
            locs = [(P.LOC_ERROR, error)] * len(spec.return_ids)
        wake = False
        escaped_hit = False
        for rid, loc in zip(spec.return_ids, locs or ()):
            rb = rid.binary()
            if rb in self._escaped:
                # Keep the mark: the flush (not the retire) consumes it
                # so the elision check below can also see it.
                escaped_hit = True
            if self._resolve_pending_locked(rb):
                wake = True
            self._cache_put_locked(rb, loc)
        if wake:
            self._cond.notify_all()
        ent = {"oids": list(spec.return_ids), "locs": list(locs or ()),
               "nested": nested or [], "error": error}
        if spec.caller_seq >= 0:
            # Settlement accounting rides the entry: the head keeps a
            # per-(actor, caller) settled store for merge-gate resyncs.
            ent["aseq"] = (spec.actor_id.binary(), spec.caller_seq)
            self._settle_seq_locked(spec.actor_id.binary(),
                                    spec.caller_seq)
        if error is None and any(
                l and l[0] == P.LOC_SHM for l in locs or ()):
            # SHM-backed results are the only ones a node death can
            # lose: ship the producing spec so the head registers
            # lineage exactly like TASK_DONE does (inline/error locs
            # live in the directory itself and never need it).
            ent["spec"] = spec
        self._done_buf.append(ent)
        if nested and any(nested):
            # Results nesting other refs register (and nested-pin)
            # immediately: deferral would widen the window in which the
            # producer's own handle drop could free the nested object
            # before the container's pin lands.
            self._flush_accounting_locked()
        elif escaped_hit:
            # The id ESCAPED while its call was still in flight (nested
            # in this worker's own task result, pinned as an arg of a
            # head submit or put): the head — or another worker behind
            # it — is already waiting on the entry, and an idle worker
            # has no future barrier, so parking here would leave that
            # wait hanging forever. Escapes AFTER retirement always
            # pass a barrier themselves (submit/put/completion drain
            # the buffer), so the steady-state call-and-drop burst
            # still parks.
            self._flush_accounting_locked()

    def _on_actor_results(self, chan, payloads: List[dict]) -> None:
        """Retire a burst of inline results in ONE critical section;
        steady state ships the head NOTHING here — the parked entries
        drain in batches at the next accounting barrier (or on the
        size-threshold overflow)."""
        finished = []
        cbs = []
        cwid = getattr(chan, "callee_wid", None)
        with self._cond:
            for payload in payloads:
                tid = payload["t"]
                spec = chan.inflight.pop(tid, None) \
                    if isinstance(chan, _DirectChannel) else None
                if spec is None:
                    continue  # reconciled already (channel raced down)
                finished.append(spec)
                if spec.streaming:
                    cbs.extend(self._retire_stream_locked(
                        spec, payload.get("streamed") or 0,
                        payload.get("error"), cwid))
                else:
                    self._retire_locked(
                        spec, payload.get("results"),
                        payload.get("error"), payload.get("nested"))
            self._n_results += len(finished)
            if len(self._done_buf) >= self._done_flush_n:
                self._flush_accounting_locked()
        for spec in finished:
            self._unpin_once(spec)
        for cb in cbs:
            try:
                cb()
            except Exception:  # lint: broad-except-ok user stream-done callback; completion must reach every waiter
                logger.debug("stream done-callback raised", exc_info=True)

    # ------------------------------------------------------------------
    # caller side: streaming generators on the channel
    # ------------------------------------------------------------------
    def _on_gen_items(self, chan, payloads: List[dict]) -> None:
        """A burst of streamed items from the callee: cache each item's
        location locally (channel FIFO ⇒ index order ⇒ no lost or
        duplicated items), count it caller-locally, wake waiters ONCE.
        The head hears nothing here — accounting ships in one entry at
        terminal registration."""
        from .ids import TaskID, object_id_for_return
        wake = False
        with self._cond:
            for p in payloads:
                tb = p["t"]
                st = self._streams.get(tb)
                if st is None:
                    continue  # stream reconciled/released already
                oid = object_id_for_return(TaskID(tb), p["i"])
                ob = oid.binary()
                self._cache_put_locked(ob, p["loc"])
                self._refs[ob] = 1
                if refdebug.enabled:
                    refdebug.borrow("direct.gen_item", oid)
                st["items"].append((oid, p["loc"],
                                    list(p.get("nested") or ())))
                st["count"] = max(st["count"], p["i"] + 1)
                wake = True
            if wake:
                self._cond.notify_all()

    def _retire_stream_locked(self, spec, streamed: int, error,
                              callee_wid=None) -> List:
        """Terminal registration of one channel stream (caller holds
        _cond): ONE accounting entry covering every arrived item (locs,
        nested ids, residual refcounts popped at flush — "head-side
        accounting only at terminal registration"), stream state
        flipped finished, done-callbacks returned for the caller to run
        outside the lock. Items yielded before a failure stay readable;
        the error surfaces once the consumer passes them (head-path
        semantics)."""
        tb = spec.task_id.binary()
        st = self._streams.get(tb)
        items = st["items"] if st is not None else []
        ent = {"oids": [it[0] for it in items],
               "locs": [it[1] for it in items],
               "nested": [it[2] for it in items], "error": None,
               # Head-side stream closure: the head folds this into its
               # own _gen_streams so a generator handle passed to the
               # driver (or another worker) resolves there too — its
               # foreign gen_wait terminates instead of hanging.
               "gen": (spec.task_id, st["count"] if st else 0),
               "stream_error": error}
        if spec.caller_seq >= 0:
            ent["aseq"] = (spec.actor_id.binary(), spec.caller_seq)
            self._settle_seq_locked(spec.actor_id.binary(),
                                    spec.caller_seq)
        if error is None and any(
                l and l[0] == P.LOC_SHM for l in ent["locs"]):
            # Same invariant as _retire_locked: SHM-backed items carry
            # their producing spec so the head registers lineage and a
            # node loss leaves them reconstructable, not dead.
            ent["spec"] = spec
        if telemetry.enabled and error is not None:
            # Mid-stream death: the callee may never report a terminal
            # event for this stream — record the caller-side FAILED so
            # the state row terminates (successful terminals flow as
            # the callee's own worker events).
            self._worker.record_stream_failed_event(spec, callee_wid)
        if st is not None and st.get("abandoned"):
            # Consumer already dropped the generator: balance the
            # unconsumed items' arrival counts BEFORE the flush pops
            # residuals — they net zero (or register-then-free for SHM
            # backing) in THIS flush, instead of parking a -1 in the
            # delta buffer with no later barrier on an idle worker.
            released = st.get("released_at", 0)
            for oid, _loc, _n in items[released:]:
                ob = oid.binary()
                if ob in self._refs:
                    self._refs[ob] -= 1
                    if refdebug.enabled:
                        refdebug.absorb("direct.stream_abandoned",
                                        oid, -1)
                else:
                    ent2 = self._ref_buf.get(ob)
                    if ent2 is None:
                        self._ref_buf[ob] = [oid, -1]
                    else:
                        ent2[1] -= 1
                    if refdebug.enabled:
                        refdebug.park("direct.stream_abandoned", oid, -1)
        self._done_buf.append(ent)
        # Items escaped nothing mid-stream (they resolve locally), but
        # the head must register them promptly: a generator consumed on
        # another worker via a passed ref, or abandoned items needing
        # the freed-path, both route through the head's directory.
        self._flush_accounting_locked()
        cbs: List = []
        if st is not None:
            st["finished"] = True
            if error is not None:
                st["error"] = error
            cbs, st["cbs"] = list(st.get("cbs", ())), []
            if st.get("abandoned"):
                self._streams.pop(tb, None)
        self._cond.notify_all()
        return cbs

    def gen_wait(self, task_id, index: int, timeout=None):
        """Caller-side mirror of Node.gen_wait for channel streams:
        (available, finished_count, error_blob). Returns None when the
        task is not a channel stream (the caller falls back to the
        head's stream state)."""
        _bump()
        tb = task_id.binary()
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                st = self._streams.get(tb)
                if st is None:
                    return None
                if index < st["count"]:
                    return True, None, None
                if st["error"] is not None:
                    return False, st["count"], st["error"]
                if st["finished"]:
                    return False, st["count"], None
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise GetTimeoutError(
                        f"Timed out waiting for streamed item {index} "
                        f"of task {task_id.hex()}")
                self._cond.wait(timeout=min(remaining, 1.0)
                                if remaining is not None else 1.0)

    def gen_release(self, task_id, consumed: int) -> bool:
        """Consumer dropped its generator: drop unconsumed arrived
        items (their arrival count is the only count they carry) and
        mark a still-running stream abandoned so the terminal entry
        releases the rest. True when the task was a channel stream."""
        tb = task_id.binary()
        drop = []
        cancel_chan = None
        with self._cond:
            st = self._streams.get(tb)
            if st is None:
                return False
            st["released_at"] = consumed
            if st["finished"]:
                drop = [it[0] for it in st["items"][consumed:]]
                self._streams.pop(tb, None)
            else:
                st["abandoned"] = True
                # Still producing: tell the callee to stop. Items
                # already in flight when the cancel lands still arrive
                # and are balanced at terminal registration (the
                # abandoned-item path in _retire_stream_locked).
                chan = self._chans.get(st["actor"].binary())
                if isinstance(chan, _DirectChannel) and chan.alive:
                    cancel_chan = chan
        if cancel_chan is not None:
            if wiretap.enabled:
                wiretap.frame("direct", "caller", id(cancel_chan),
                              "send", P.GEN_CANCEL, {"t": tb})
            try:
                cancel_chan.writer.send_message(P.GEN_CANCEL, {"t": tb})
            except Exception:  # lint: broad-except-ok channel died under the cancel: reconcile terminates the stream anyway
                pass
        for oid in drop:
            self.ref_delta(oid, -1)
        if drop:
            self.flush_accounting()
        return True

    def gen_add_done_callback(self, task_id, cb) -> bool:
        """cb() when the channel stream finishes (now if already done).
        False when the task is not a channel stream."""
        tb = task_id.binary()
        with self._cond:
            st = self._streams.get(tb)
            if st is None:
                return False
            if not st["finished"]:
                st["cbs"].append(cb)
                return True
        cb()
        return True

    def _on_channel_down(self, chan: _DirectChannel) -> None:
        """Channel EOF/error: drain every in-flight and queued call
        through the head's reconciliation (retry-ledger bumped attempt
        accounting; requeue-or-typed-error), then pin this (caller,
        actor) pair to the head path (re-dialable after a backoff
        cooldown — see _Fallback). Streaming calls terminate HERE with
        a typed error (streams are never retryable; items already
        arrived stay readable) while their specs still ride the
        reconcile so the head records settlement and releases any merge
        gate holds referencing them."""
        if not isinstance(chan, _DirectChannel):
            return
        w = self._worker
        # Reply slot allocated up front so the RECONCILE send can happen
        # INSIDE the _cond critical section that retires the local
        # refcounts (the ordering invariant: later decrefs for these ids
        # must enqueue after the accounting that transfers them).
        fut: Future = Future()
        with w._req_lock:
            w._req_counter += 1
            req_id = w._req_counter
            w._pending[req_id] = fut  # lint: guarded-by-ok receiver is the head-link Worker, not the plane: ITS _pending is guarded by w._req_lock, held here
        stream_cbs: List = []
        with self._cond:
            if not chan.alive:
                w._pending.pop(req_id, None)  # lint: guarded-by-ok receiver is the head-link Worker: GIL-atomic pop of OUR slot, no other thread knows this req_id yet
                return
            chan.alive = False
            # Parked completion accounting registers head-side BEFORE
            # the reconcile is processed (same FIFO pipe), so the
            # head's already-landed idempotence check can see it.
            self._flush_accounting_locked()
            ab = chan.actor_id.binary()
            prior = self._chans.get(ab)
            self._chans[ab] = _Fallback(
                attempts=(prior.attempts if isinstance(prior, _Fallback)
                          else 0))
            specs = list(chan.inflight.values())
            sent = set(id(s) for s in specs)
            for s in chan.queue:
                if id(s) not in sent:
                    specs.append(s)
            chan.inflight.clear()
            chan.queue.clear()
            dead_blob = None
            deltas = []
            for spec in specs:
                ds = []
                for rid in spec.return_ids:
                    rb = rid.binary()
                    self._escaped.discard(rb)  # head takes ownership
                    if refdebug.enabled and rb in self._refs:
                        refdebug.settle("direct.reconcile", rid)
                    ds.append(self._refs.pop(rb, 0))
                deltas.append(ds)
                if spec.streaming:
                    # Mid-stream EOF: terminate now with the typed
                    # error (no return ids — the stream state IS the
                    # delivery surface), shipping the arrived items'
                    # accounting in the same critical section.
                    if dead_blob is None:
                        dead_blob = serialization.dumps(ActorDiedError(
                            f"Actor {chan.actor_id.hex()} became "
                            f"unreachable mid-stream"))
                    stream_cbs.extend(self._retire_stream_locked(
                        spec, 0, dead_blob, chan.callee_wid))
            snap = self._seq_snapshot_locked(ab)
            if specs:
                payload = {
                    "actor_id": chan.actor_id, "specs": specs,
                    "deltas": deltas, "req_id": req_id,
                    "callee_wid": chan.callee_wid}
                if snap is not None:
                    payload["settled_below"], payload["settled_set"] = \
                        snap
                if wiretap.enabled:
                    wiretap.frame("direct", "caller", id(chan), "send",
                                  P.DIRECT_RECONCILE, payload)
                    wiretap.request_sent(P.DIRECT_RECONCILE, req_id)
                try:
                    w.send(P.DIRECT_RECONCILE, payload)
                except Exception:
                    fut.set_result(None)
        chan.close()
        # Outstanding object pulls riding this channel fail NOW (typed
        # "channel_down" -> daemon-path fallback) instead of waiting out
        # the full pull deadline on a dead socket.
        with self._pull_lock:
            dead_pulls = [st for st in self._pulls.values()
                          if st.get("chan") is chan]
        for st in dead_pulls:
            if st["err"] is None:
                st["err"] = "channel_down"
            st["evt"].set()
        if telemetry.enabled:
            telemetry.record_direct_fallback("channel_down")
        for cb in stream_cbs:
            try:
                cb()
            except Exception:  # lint: broad-except-ok user stream-done callback; reconcile must proceed
                logger.debug("stream done-callback raised", exc_info=True)
        if not specs:
            w._pending.pop(req_id, None)  # lint: guarded-by-ok receiver is the head-link Worker: GIL-atomic pop of OUR slot, no other thread knows this req_id yet
            return
        try:
            out = fut.result(timeout=60.0)
        except Exception:
            out = None
        if isinstance(out, dict) and out.get("__error__") is not None:
            out = None
        with self._cond:
            for i, spec in enumerate(specs):
                res = out[i] if (isinstance(out, list)
                                 and i < len(out)) else None
                status = (res or {}).get("status")
                if spec.caller_seq >= 0:
                    if status == "requeued":
                        # Ownership moved to the head: later calls list
                        # it as a cross-plane predecessor until its
                        # retry lands.
                        sq = self._seq_state_locked(ab)
                        s = spec.caller_seq
                        if s in sq["d"]:
                            sq["d"].discard(s)
                            sq["h"].add(s)
                    else:
                        # done/failed/unknown: terminally settled (the
                        # result or error is registered head-side, or
                        # delivered locally right below).
                        self._settle_seq_locked(ab, spec.caller_seq)
                for rid in spec.return_ids:
                    rb = rid.binary()
                    self._resolve_pending_locked(rb)
                    if status in ("requeued", "done"):
                        continue  # head owns it now: resolve via head
                    blob = (res or {}).get("error") \
                        or serialization.dumps(ActorDiedError(
                            f"Actor {chan.actor_id.hex()} became "
                            f"unreachable with direct calls in flight"))
                    self._cache_put_locked(rb, (P.LOC_ERROR, blob))
            self._cond.notify_all()
        for spec in specs:
            self._unpin_once(spec)

    # ------------------------------------------------------------------
    # callee side
    # ------------------------------------------------------------------
    def on_channel_open(self, payload: dict) -> None:
        """CHANNEL_OPEN from the head: make sure the listener exists and
        report its endpoints (oneway CHANNEL_ADDR, matched by token)."""
        try:
            info = self._ensure_listener()
            reply = dict(info)
            reply["token"] = payload.get("token")
            reply["error"] = None
        except Exception as e:
            reply = {"token": payload.get("token"), "error": repr(e)}
        try:
            self._worker.send_lazy(P.CHANNEL_ADDR, reply)
        except Exception:  # lint: broad-except-ok head pipe dead: broker times out and refuses the channel
            pass

    def _ensure_listener(self) -> dict:
        with self._listen_lock:
            if self._listener_info is not None:
                return self._listener_info
            from multiprocessing.connection import Listener
            from .config import ray_config
            key = os.urandom(16)
            wid = self._worker.config.worker_id.hex()
            path = os.path.join(self._worker.config.session_dir,
                                f"d_{wid[:16]}.sock")
            try:
                os.unlink(path)
            except OSError:
                pass
            unix_l = Listener(path, family="AF_UNIX", authkey=key)
            self._listeners.append(unix_l)
            threading.Thread(target=self._accept_loop, args=(unix_l,),
                             daemon=True, name="direct-accept-unix").start()
            tcp = None
            try:
                host = str(ray_config.node_host)
                tcp_l = Listener((host, 0), family="AF_INET", authkey=key)
                self._listeners.append(tcp_l)
                tcp = tcp_l.address
                threading.Thread(target=self._accept_loop, args=(tcp_l,),
                                 daemon=True,
                                 name="direct-accept-tcp").start()
            except OSError:
                tcp = None  # UNIX-only host: same-node callers only
            self._listener_info = {
                "unix": path, "tcp": tcp, "key": key.hex(),
                "worker_id": wid,
                "node": self._worker.config.node_id_hex}
            return self._listener_info

    def _accept_loop(self, listener) -> None:
        while True:
            try:
                conn = listener.accept()
            except (OSError, EOFError):
                return
            except Exception:
                # A failed auth handshake must not kill the acceptor.
                logger.debug("direct accept failed", exc_info=True)
                continue
            try:
                from .netcomm import tune_control_socket
                tune_control_socket(conn.fileno())
            except Exception:  # lint: broad-except-ok socket tuning is best-effort on non-TCP conns (same as netcomm)
                pass
            _ServeConn(self, conn)

    @staticmethod
    def _wire_spec(payload: dict):
        spec = payload.get("spec")
        if spec is not None:
            return spec
        tb, ab, mn, name, rids, nr, fid, cid, cseq, preds, tctx = \
            payload["c"]
        from .ids import ActorID, ObjectID, TaskID
        return P.TaskSpec(
            task_id=TaskID(tb), fn_id=fid, fn_blob=None,
            return_ids=[ObjectID(b) for b in rids], num_returns=nr,
            name=name, actor_id=ActorID(ab), method_name=mn,
            caller_id=cid, caller_seq=cseq, seq_preds=preds,
            trace_ctx=tctx)

    def _on_actor_call(self, chan, payload: dict) -> None:
        """One ACTOR_CALL landed on the callee: route it through the
        actor's normal (ordered / concurrency-grouped) executors with
        the result bound back to this channel."""
        self._on_actor_calls(chan, [payload])

    def _on_actor_calls(self, chan, payloads: List[dict]) -> None:
        """A burst of calls from one caller. The common shape —
        max_concurrency=1 actor, no concurrency groups, no trace
        context — runs the whole run as ONE lean executor item
        (worker_proc._execute_direct_batch), amortizing the
        submit/Future machinery the head path pays per task; anything
        else takes the full _execute path per spec."""
        w = self._worker
        specs = [self._wire_spec(p) for p in payloads]
        if w._actor_instance is None or w._actor_executor is None:
            blob = serialization.dumps(ActorDiedError(
                "direct call reached a worker that hosts no live actor"))
            for spec in specs:
                self.send_result(chan, {
                    "task_id": spec.task_id, "results": None,
                    "error": blob, "actor_id": spec.actor_id,
                    "return_oids": list(spec.return_ids)})
            return
        aspec = w._actor_spec
        if (aspec is not None and aspec.max_concurrency == 1
                and not w._cg_executors
                and all(not s.streaming
                        and s.method_name not in ("__adag_exec_loop__",
                                                  PROFILE_METHOD)
                        for s in specs)):
            # Traced calls stay on this lean path too — the batch
            # executor adopts each spec's trace context itself.
            # The merge gate sequences stamped bursts against head-path
            # arrivals from the same caller; contiguous admissible runs
            # still ship as ONE lean executor item.
            w.seq_gate_admit_burst(
                specs,
                lambda batch: w._actor_executor.submit(
                    w._execute_direct_batch, chan, batch))
            return
        for spec in specs:
            spec.__dict__["_direct_chan"] = chan
            w._handle_exec(spec)

    def _tag_locs(self, locs):
        node = self._worker.config.node_id_hex
        if not node or not locs:
            return locs
        return [(P.LOC_SHM, l[1], node)
                if (l and l[0] == P.LOC_SHM and len(l) < 3) else l
                for l in locs]

    def send_gen_item(self, chan, task_id, index: int, loc,
                      nested) -> None:
        """Ship one streamed item callee->caller on the channel (node-
        tagged like inline results, so cross-node callers can pull the
        SHM backing). Send failures propagate: the caller is gone and
        the executing generator aborts into the error path."""
        payload = {
            "t": task_id.binary(), "i": index,
            "loc": self._tag_locs([loc])[0], "nested": nested}
        if wiretap.enabled:
            wiretap.frame("direct", "callee", id(chan), "send",
                          P.GEN_ITEM, payload)
        chan.writer.send_message(P.GEN_ITEM, payload)

    def send_result(self, chan, payload: dict) -> None:
        """Ship one completed direct call's result back to the caller;
        if the caller is gone, fall back to head accounting so ids that
        escaped the caller still resolve (DIRECT_DONE, zero residual)."""
        locs = self._tag_locs(payload.get("results"))
        payload["results"] = locs
        try:
            msg = {"t": payload["task_id"].binary(), "results": locs,
                   "error": payload.get("error"),
                   "nested": payload.get("nested")}
            if payload.get("streamed") is not None:
                # Terminal frame of a channel stream: the caller
                # registers the arrived items with the head here.
                msg["streamed"] = payload["streamed"]
            if wiretap.enabled:
                wiretap.frame("direct", "callee", id(chan), "send",
                              P.ACTOR_RESULT, msg)
            chan.writer.send_message(P.ACTOR_RESULT, msg)
            return
        except Exception:  # lint: broad-except-ok caller gone: fall through to head-accounting fallback below
            pass
        entry = {"oids": list(payload.get("return_oids") or ()),
                 "locs": list(payload.get("results") or ()),
                 "nested": payload.get("nested") or [],
                 "deltas": [0] * len(payload.get("return_oids") or ()),
                 "error": payload.get("error")}
        if payload.get("error") is None and payload.get("spec") \
                is not None and any(l and l[0] == P.LOC_SHM
                                    for l in locs or ()):
            # Same invariant as the caller-side flush: SHM results
            # carry their producing spec so escaped refs survive node
            # loss via lineage even when the caller itself is gone.
            entry["spec"] = payload["spec"]
        try:
            self._worker.send_lazy(P.DIRECT_DONE, {"entries": [entry]})
        except Exception:  # lint: broad-except-ok head pipe dead too: the process is exiting, nothing left to tell
            pass

    # ------------------------------------------------------------------
    # serve data plane (callee side): SERVE_REQ in, SERVE_RESP out.
    # Ownership-free by construction — no task id, no return-object
    # registration, no sequencing: the proxy is the only consumer and
    # the channel the only route, so the head hears NOTHING per request
    # (cheaper than even the batched DIRECT_DONE accounting actor calls
    # pay). Bodies above serve_direct_body_threshold move through the
    # shared same-node arena instead of the frame (serve_encode_body).
    # ------------------------------------------------------------------
    def _on_serve_req(self, chan, payload: dict) -> None:
        """One serve request from a proxy landed on this replica's
        worker: run it on the actor's executor pool with the response
        bound back to this channel."""
        _bump()
        w = self._worker
        if w._actor_instance is None or w._actor_executor is None:
            blob = serialization.dumps(ActorDiedError(
                "serve request reached a worker that hosts no live actor"))
            resp = {"r": payload.get("r"), "e": blob}
            if wiretap.enabled:
                wiretap.frame("direct", "callee", id(chan), "send",
                              P.SERVE_RESP, resp)
            try:
                chan.writer.send_message(P.SERVE_RESP, resp)
            except Exception:  # lint: broad-except-ok proxy hung up: its channel EOF fails the request typed
                pass
            return
        w._actor_executor.submit(self._serve_exec, chan, payload)

    def _serve_exec(self, chan, payload: dict) -> None:
        """Executor-side runner for one SERVE_REQ (the relevant slice
        of worker_proc._execute: trace adoption, coroutine bridging,
        TaskError packaging — same failure semantics as the head path
        so the proxy's error handling cannot tell the planes apart)."""
        import inspect
        import traceback

        from ..exceptions import TaskError
        from ..util import tracing
        w = self._worker
        msg: Dict[str, Any] = {"r": payload.get("r")}
        trace_token = exec_span = None
        if payload.get("tr"):
            try:
                trace_token = tracing.activate_context(payload["tr"])  # lint: ungated-instrumentation-ok gated by the payload trace-ctx check
                exec_span = tracing.span(  # lint: ungated-instrumentation-ok same payload trace-ctx gate
                    "serve:direct_exec",
                    worker_id=w.config.worker_id.hex())
                exec_span.__enter__()
            except Exception:
                trace_token = exec_span = None
        try:
            (args, kwargs), free_ob = serve_decode_body(
                w.store, payload["b"])
            if free_ob is not None:
                # Request body was arena-staged by the proxy: ack so it
                # can release the slot (oneway, coalesces with the
                # response frame on the writer).
                if wiretap.enabled:
                    wiretap.frame("direct", "callee", id(chan), "send",
                                  P.SERVE_BODY_FREE, {"o": free_ob})
                chan.writer.send_message(P.SERVE_BODY_FREE,
                                         {"o": free_ob})
            method = getattr(w._actor_instance,
                             payload.get("m") or "handle_request")
            result = method(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = w._run_coroutine(result)
            msg["v"] = serve_encode_body(w.store, result,
                                         bool(payload.get("sn")))
            if exec_span is not None:
                trace_token = w._trace_exit(trace_token, exec_span)
                exec_span = None
        except BaseException as e:  # noqa: BLE001 — ships to the proxy
            err = TaskError(e, task_repr=f"serve:{payload.get('m')}",
                            remote_tb=traceback.format_exc())
            try:
                msg["e"] = serialization.dumps(err)
            except Exception:
                msg["e"] = serialization.dumps(TaskError(
                    RuntimeError(repr(e)), task_repr="serve"))
            if exec_span is not None:
                trace_token = w._trace_exit(trace_token, exec_span, e)
                exec_span = None
        finally:
            if exec_span is not None or trace_token is not None:
                w._trace_exit(trace_token, exec_span)
        if wiretap.enabled:
            wiretap.frame("direct", "callee", id(chan), "send",
                          P.SERVE_RESP, msg)
        try:
            chan.writer.send_message(P.SERVE_RESP, msg)
        except Exception:  # lint: broad-except-ok proxy gone: reclaim the staged body, nothing else to tell
            enc = msg.get("v")
            if enc is not None and enc[0] == "o":
                from .ids import ObjectID
                try:
                    w.store.free(ObjectID(enc[1]))
                except Exception:  # lint: broad-except-ok teardown race; the arena dies with the session anyway
                    pass

    def _on_serve_body_free(self, payload: dict) -> None:
        """Oneway: the peer finished decoding an arena-staged body this
        process produced — release the slot (the arena delete retries
        behind live reader pins, so free-while-read stays safe)."""
        _bump()
        from .ids import ObjectID
        try:
            self._worker.store.free(ObjectID(payload["o"]))
        except Exception:  # lint: broad-except-ok double-free after teardown is harmless
            pass

    # ------------------------------------------------------------------
    # direct object transfer plane: worker<->worker pulls over the
    # brokered channels (reference: the object manager's Push/Pull
    # chunked transfers between the owning processes,
    # object_manager/object_manager.cc — never through a central
    # broker). A PULL_DIRECT on the (caller, owner-node worker) channel
    # is answered by ranged OBJ_CHUNK frames whose payload bytes ride
    # as pickle-5 OUT-OF-BAND views of the sealed store segment
    # (separate iovecs of the writer's vectored write — no pickling of
    # payload bytes, no intermediate buffer), terminated by OBJ_EOF.
    # Ownership-free: a pull replicates sealed bytes, no refcounts
    # move. EVERY failure path returns the caller to the daemon-relayed
    # PULL_OBJECT route unchanged.
    # ------------------------------------------------------------------
    def _channel_to_node(self, node_hex: str):
        """Any live channel to a worker on `node_hex`: object locations
        are node-scoped (every worker maps the node-shared store), so
        any direct peer on the owning node can serve the bytes."""
        with self._cond:
            for chan in self._chans.values():
                if isinstance(chan, _DirectChannel) and chan.alive \
                        and chan.node_hex == node_hex:
                    return chan
        return None

    def _link_gate(self, node_hex: str):
        """Per-peer-node semaphore bounding this process's concurrent
        direct pulls on one link (`shuffle_link_inflight`; 0 = no
        gate). Motivated by the shuffle exchange — a reduce that fans
        pulls at every producer node at once would otherwise stampede
        one peer past its direct_transfer_max_serving admission cap
        and degrade whole shard sets to the daemon relay — but applied
        to every direct pull: the cap is a property of the link, not
        of who pulls. Returns the semaphore or None."""
        from .config import ray_config
        cap = int(ray_config.shuffle_link_inflight)
        if cap <= 0:
            return None
        with self._pull_lock:
            sem = self._link_sems.get(node_hex)
            if sem is None:
                sem = self._link_sems[node_hex] = \
                    threading.BoundedSemaphore(cap)
        return sem

    def pull_object(self, object_id, node_hex: str,
                    size_hint: int = 0) -> bool:
        """Pull one remote object worker-to-worker over an already-
        brokered direct channel (the object-transfer fast path). True
        => the object arrived sealed in the local store. ANY failure —
        no channel to the owning node, channel death mid-transfer,
        gapped chunks, owner-side miss, deadline — returns False and
        the caller takes the daemon PULL_OBJECT path unchanged. With
        direct_object_transfer_enabled off this returns before ANY
        work, counter-proven by the flag-off perf_smoke guard."""
        from .config import ray_config
        if not self.enabled or not bool(
                ray_config.direct_object_transfer_enabled):
            return False
        if size_hint and size_hint < int(
                ray_config.direct_transfer_min_bytes):
            return False
        chan = self._channel_to_node(node_hex)
        if chan is None:
            return False
        key = object_id.binary()
        with self._pull_lock:
            racer = self._inflight_pulls.get(key)
            if racer is None:
                self._inflight_pulls[key] = threading.Event()
        if racer is not None:
            # Another thread of this process is already pulling this
            # object: wait for it rather than double-reserving the id
            # (the loser's reserve would collide on the store segment).
            deadline = float(ray_config.pull_deadline_s)
            racer.wait(deadline if deadline > 0 else 30.0)
            try:
                return self._worker.store.contains(object_id)
            except Exception:  # lint: broad-except-ok containment probe; False falls back to the daemon path
                return False
        gate = self._link_gate(node_hex)
        if gate is not None:
            # Pace, never wedge: a gate slot outlives at most one pull
            # deadline, so waiting that long means the link is fully
            # saturated with pulls that will all release — and if the
            # wait still times out, proceed ungated rather than fail
            # (the gate is an optimization, not a correctness fence).
            deadline = float(ray_config.pull_deadline_s)
            if not gate.acquire(timeout=deadline if deadline > 0 else 30.0):
                gate = None
        try:
            return self._pull_object_gated(object_id, node_hex, chan)
        finally:
            if gate is not None:
                gate.release()
            with self._pull_lock:
                done = self._inflight_pulls.pop(key, None)
            if done is not None:
                done.set()

    def _pull_object_gated(self, object_id, node_hex: str, chan) -> bool:
        from .config import ray_config
        _bump()
        global _pull_ops
        _pull_ops += 1
        st = {"evt": threading.Event(), "oid": object_id, "chan": chan,
              "view": None, "res": None, "next": 0, "got": 0,
              "total": None, "err": None, "ok": False}
        with self._pull_lock:
            self._pull_seq += 1
            rid = self._pull_seq
            self._pulls[rid] = st
        if telemetry.enabled:
            telemetry.record_transfer_inflight(1)
        try:
            # Inside the try: an injected fault falls back to the
            # daemon path like any real transfer failure would.
            if fault.enabled:
                fault.fire("direct.pull", obj=object_id.hex()[:8])
            req = {"r": rid, "o": object_id.binary()}
            if wiretap.enabled:
                wiretap.frame("direct", "caller", id(chan), "send",
                              P.PULL_DIRECT, req)
            chan.writer.send_message(P.PULL_DIRECT, req)
            deadline = float(ray_config.pull_deadline_s)
            if not st["evt"].wait(deadline if deadline > 0 else None):
                st["err"] = st["err"] or "deadline"
        except Exception:
            logger.debug("direct pull request failed", exc_info=True)
            st["err"] = st["err"] or "send"
        finally:
            with self._pull_lock:
                self._pulls.pop(rid, None)
            if telemetry.enabled:
                telemetry.record_transfer_inflight(-1)
        ok = bool(st["ok"]) and st["err"] is None
        if not ok:
            self._abort_pull_state(st)
            if telemetry.enabled:
                telemetry.record_direct_fallback(
                    f"pull:{st['err'] or 'error'}")
            logger.debug("direct pull of %s from node %s failed (%s); "
                         "falling back to the daemon path",
                         object_id.hex()[:8], (node_hex or "?")[:8],
                         st["err"])
        elif telemetry.enabled and st["total"]:
            telemetry.record_transfer_bytes(st["total"])
        return ok

    def _abort_pull_state(self, st: dict) -> None:
        """Unwind a failed pull's partially written segment so the
        daemon-path fallback starts from a clean store."""
        if st.get("view") is None:
            return
        try:
            st["view"].release()
        except Exception:  # lint: broad-except-ok view already released by the failing writer path
            pass
        st["view"] = None
        res, st["res"] = st.get("res"), None
        try:
            if res is not None:
                # Reservation abort: pops the segment and unlinks the
                # partial file with no spill round trip — tighter than
                # free() for a never-sealed object.
                res.abort()
            else:
                self._worker.store.free(st["oid"])
        except Exception:  # lint: broad-except-ok partial-segment cleanup; the daemon path re-creates the id
            pass

    def _on_obj_chunk(self, chan, payload: dict) -> None:
        """One ranged chunk of an in-flight pull (channel recv thread):
        copy the out-of-band payload view straight into the
        preallocated store segment. Chunks must arrive gapless and
        in order — the channel is FIFO, so a gap means protocol skew
        and fails the pull typed."""
        rid, idx, off, total, data = payload["c"]
        with self._pull_lock:
            st = self._pulls.get(rid)
        if st is None or st["err"] is not None:
            return  # abandoned pull (deadline/channel down): drop
        try:
            if idx != st["next"] or off != st["got"]:
                raise RuntimeError(
                    f"gapped chunk {idx}@{off} (expected "
                    f"{st['next']}@{st['got']})")
            if st["view"] is None:
                if idx != 0:
                    raise RuntimeError("stream started mid-object")
                st["total"] = int(total)
                # Same reserve/seal protocol as the local put path
                # (object_store.reserve): pool-recycled segments land
                # pulls into pre-faulted pages too.
                st["res"] = self._worker.store.reserve(
                    st["oid"], int(total))
                st["view"] = st["res"].view()
            # NT-store copy (object_store.copy_into): a pulled object
            # is written once here and read by the task later, often
            # from another process — the same no-write-allocate
            # argument as the put path.
            n = object_store.copy_into(st["view"], off, data)
            st["got"] += n
            st["next"] = idx + 1
        except Exception as e:  # lint: broad-except-ok any receive-side failure (store full, id collision, skew) fails the pull typed; the daemon path remains
            logger.debug("direct pull chunk failed", exc_info=True)
            st["err"] = repr(e)
            st["evt"].set()

    def _on_obj_eof(self, chan, payload: dict) -> None:
        """Pull terminal frame: seal on a complete byte count, fail
        typed otherwise (owner refusal, short stream)."""
        with self._pull_lock:
            st = self._pulls.get(payload.get("r"))
        if st is None:
            return
        if payload.get("ok") and st["err"] is None \
                and st["total"] is not None \
                and st["got"] == st["total"]:
            try:
                if st["view"] is not None:
                    st["view"].release()
                    st["view"] = None
                res, st["res"] = st.get("res"), None
                if res is not None:
                    res.seal()
                else:
                    self._worker.store.seal(st["oid"])
                st["ok"] = True
            except Exception as e:  # lint: broad-except-ok seal failure downgrades to the daemon path, never raises on the recv thread
                st["err"] = repr(e)
        elif st["err"] is None:
            st["err"] = payload.get("e") or "incomplete"
        st["evt"].set()

    # -- callee (serving) side ----------------------------------------
    def _transfer_executor(self):
        exec_ = self._xfer_exec
        if exec_ is None:
            from concurrent.futures import ThreadPoolExecutor

            from .config import ray_config
            with self._pull_lock:
                if self._xfer_exec is None:
                    self._xfer_exec = ThreadPoolExecutor(
                        max_workers=max(1, int(
                            ray_config.direct_transfer_max_serving)),
                        thread_name_prefix="direct-xfer")
                exec_ = self._xfer_exec
        return exec_

    def _send_pull_eof(self, chan, rid, ok: bool,
                       err: Optional[str] = None) -> None:
        msg: Dict[str, Any] = {"r": rid, "ok": bool(ok)}
        if err is not None:
            msg["e"] = err
        if wiretap.enabled:
            wiretap.frame("direct", "callee", id(chan), "send",
                          P.OBJ_EOF, msg)
        try:
            chan.writer.send_message(P.OBJ_EOF, msg)
        except Exception:  # lint: broad-except-ok puller hung up: its channel EOF fails the pull client-side
            pass

    def _on_pull_direct(self, chan, payload: dict) -> None:
        """One PULL_DIRECT landed on this worker: serve the bytes back
        as ranged OBJ_CHUNK frames off the dedicated transfer pool.
        Admission past direct_transfer_max_serving refuses typed (the
        caller falls back to the daemon path) so bulk pulls cannot
        starve each other or the channel."""
        _bump()
        from .config import ray_config
        with self._pull_lock:
            admitted = self._serving_pulls < max(
                1, int(ray_config.direct_transfer_max_serving))
            if admitted:
                self._serving_pulls += 1
        if not admitted:
            self._send_pull_eof(chan, payload.get("r"), ok=False,
                                err="busy")
            return
        try:
            self._transfer_executor().submit(
                self._pull_serve_exec, chan, payload)
        except BaseException:
            with self._pull_lock:
                self._serving_pulls -= 1
            self._send_pull_eof(chan, payload.get("r"), ok=False,
                                err="submit")
            raise

    def _pull_serve_exec(self, chan, payload: dict) -> None:
        """Transfer-pool runner for one PULL_DIRECT: ranged OBJ_CHUNK
        frames whose payload bytes are out-of-band views of the sealed
        segment mapping (or of its spill-file mapping — a cold object
        streams straight from the spill file without re-admission).
        The writer's byte-bounded backpressure is the flow control:
        enqueueing blocks once 64 MB is in flight, so a slow puller
        throttles the serve instead of ballooning this process."""
        import pickle as _pickle

        from .config import ray_config
        from .ids import ObjectID
        rid = payload.get("r")
        w = self._worker
        if telemetry.enabled:
            telemetry.record_transfer_inflight(1)
        try:
            try:
                view = w.store.get_raw(ObjectID(payload["o"]))
            except Exception:  # lint: broad-except-ok any store miss (freed, foreign backend) refuses typed; the caller falls back to the daemon path
                self._send_pull_eof(chan, rid, ok=False, err="miss")
                return
            total = view.nbytes
            if total <= 0:
                self._send_pull_eof(chan, rid, ok=False, err="empty")
                return
            chunk = max(1 << 16, int(float(
                ray_config.direct_transfer_chunk_mb) * (1 << 20)))
            off = 0
            idx = 0
            try:
                while off < total:
                    n = min(chunk, total - off)
                    body = {"c": (rid, idx, off, total,
                                  _pickle.PickleBuffer(
                                      view[off:off + n]))}
                    if wiretap.enabled:
                        wiretap.frame("direct", "callee", id(chan),
                                      "send", P.OBJ_CHUNK, body)
                    chan.writer.send_message(P.OBJ_CHUNK, body)
                    off += n
                    idx += 1
            except Exception:  # lint: broad-except-ok puller hung up mid-stream: its channel EOF fails the pull client-side; nothing to unwind here
                logger.debug("direct pull serve aborted", exc_info=True)
                return
            self._send_pull_eof(chan, rid, ok=True)
        finally:
            with self._pull_lock:
                self._serving_pulls -= 1
            if telemetry.enabled:
                telemetry.record_transfer_inflight(-1)


# ---------------------------------------------------------------------------
# Serve body codec, shared by BOTH ends of the serve data plane (the
# callee above and serve/_private/direct_client.py): one encoding policy
# so the planes cannot diverge.
def _serve_stage_path(store):
    """This process's same-node staging identity: the shared arena file
    for ArenaObjectStore, the shm segment dir for the file-per-object
    store (ObjectStore._path is a method, the arena's is a str). Path
    equality on the consumer side means 'I can map the producer's
    bytes in place'."""
    p = getattr(store, "_path", None)
    if isinstance(p, str):
        return p
    return getattr(store, "_dir", None)


def serve_encode_body(store, value, same_node: bool):
    """Encode one serve request/response payload for a channel frame.

    Small payloads pickle inline (("i", bytes)). Payloads above
    serve_direct_body_threshold between same-node processes stage in
    the node store (("o", oid, path, size)): the producer writes once,
    the consumer maps the same bytes read-only — the body never enters
    the frame, and never pickles twice. The consumer acks with
    SERVE_BODY_FREE and the producer frees its slot. Any staging
    failure degrades to inline (always correct)."""
    sobj = serialization.serialize(value)
    from .config import ray_config
    thr = int(ray_config.serve_direct_body_threshold)
    spath = _serve_stage_path(store) if same_node else None
    if spath and thr > 0 and sobj.total_size > thr:
        from .ids import ObjectID
        oid = ObjectID.from_random()
        try:
            store.put_serialized(oid, sobj)
            return ("o", oid.binary(), spath, sobj.total_size)
        except Exception:  # lint: broad-except-ok store full/contended: inline is always correct
            pass
    return ("i", sobj.to_bytes())


def serve_decode_body(store, enc):
    """Decode one frame body; returns (value, free_oid_bytes). A
    non-None free oid means the body was store-staged: the caller must
    ship SERVE_BODY_FREE back to the producer once decoded. Arena
    same-path consumers read the shared arena under a per-read pin;
    file-store consumers map the segment by its deterministic path and
    release their reader mapping after decode (live zero-copy views
    park the mapping in the graveyard); a same-host consumer with its
    OWN arena adopts the producer slot in place for the read."""
    if enc[0] == "i":
        return serialization.deserialize(enc[1]), None
    _kind, ob, path, size = enc
    from .ids import ObjectID
    oid = ObjectID(ob)
    if getattr(store, "_path", None) == path:
        value = serialization.deserialize(store.get_raw(oid))
        return value, ob
    if getattr(store, "_dir", None) == path:
        try:
            value = serialization.deserialize(store.get_raw(oid))
        finally:
            store.release(oid)
        return value, ob
    store.adopt_native(oid, path, 0, size, pin=True)
    try:
        value = serialization.deserialize(store.get_raw(oid))
    finally:
        store.free_external_entry(oid)
    return value, ob
