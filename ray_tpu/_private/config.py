"""Runtime configuration (reference: the RAY_CONFIG flag system —
src/ray/common/ray_config_def.h, 221 `RAY_CONFIG(type, name, default)`
entries overridable via `RAY_<name>` env vars, mirrored to Python
through includes/ray_config.pxi; SURVEY.md §5 config tiers).

Every entry is overridable via `RAY_TPU_<NAME>` (upper-cased) in the
environment of the process that starts the runtime. Booleans accept
0/1/true/false. Access through the singleton:

    from ray_tpu._private.config import ray_config
    ray_config.inline_object_max_bytes
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict


def _coerce(value: str, default: Any) -> Any:
    if isinstance(default, bool):
        return value.strip().lower() in ("1", "true", "yes", "on")
    return type(default)(value)


class RayConfig:
    """Typed, env-overridable runtime knobs (one instance per process).

    Defaults here are the single source of truth for magic numbers the
    runtime used to hard-code.
    """

    _DEFAULTS: Dict[str, Any] = {
        # objects below this size ride inline in control messages
        # (reference: max_direct_call_object_size)
        "inline_object_max_bytes": 100 * 1024,
        # object store capacity, when not set explicitly, as a fraction of
        # what /dev/shm has free, within physical memory and the process's
        # rlimits (reference: object_store_memory default 30%)
        "object_store_memory_fraction": 0.5,
        # worker boot: seconds to wait for the process to connect
        "worker_register_timeout_s": 60.0,
        # task event log cap (reference: task_events_max_num... family)
        "max_task_events": 10_000,
        # tracing span store cap (global, across all per-trace rings:
        # the oldest trace is evicted whole past this)
        "max_spans": 20_000,
        # per-trace span ring capacity in the head store (drop-oldest
        # with an exact per-trace counter)
        "max_spans_per_trace": 4096,
        # per-process bounded span buffer (drained onto TASK_EVENTS
        # frames / the driver's in-process flush; drop-oldest beyond)
        "span_buffer_size": 2048,
        # default task max_retries (reference: task_retry defaults)
        "default_task_max_retries": 3,
        # freed-object release broadcast coalescing window
        "release_broadcast_delay_s": 0.002,
        # session dir GC age threshold
        "session_gc_max_age_s": 6 * 3600.0,
        # client server default port
        "client_server_port": 10001,
        # dashboard default port (reference: 8265)
        "dashboard_port": 8265,
        # usage/telemetry opt-out (reference: RAY_USAGE_STATS_ENABLED)
        "usage_stats_enabled": False,
        # -- telemetry plane (_private/telemetry.py; on/off itself is
        # RAY_TPU_TELEMETRY, mirroring RAY_TPU_FAULT_CONFIG) -------------
        # Per-worker task-event buffer capacity; overflow drops oldest
        # with an exact counter (reference: task_event_buffer.h bound).
        "task_event_buffer_size": 4096,
        # Min seconds between a worker's piggybacked metrics snapshots.
        "worker_metrics_push_interval_s": 2.0,
        # -- object spilling (reference: object_spilling_config,
        #    LocalObjectManager) -----------------------------------------
        "object_spilling_enabled": True,
        # Spill target URI routed through pyarrow.fs ("" = the session-
        # local spill directory). file://, gs://, s3:// — TPU VMs with
        # small local disks spill to object storage (reference:
        # object_spilling_config URIs incl. S3).
        "object_spilling_path": "",
        # objects below this size stay in shm (reference default 100 MiB;
        # small here so capacity-bounded test stores can spill anything)
        "min_spilling_size": 0,
        # -- memory monitor / OOM killer (reference: memory_monitor.h:52,
        #    memory_usage_threshold, worker_killing_policy.h:34) ---------
        "memory_usage_threshold": 0.95,
        "memory_monitor_refresh_ms": 250,
        # retriable_lifo (kill newest retriable first) | group_by_owner
        "worker_killing_policy": "retriable_lifo",
        # sqlite file for durable GCS KV ("" = in-memory only; reference:
        # Redis-backed GCS fault tolerance, store_client/redis_store_client)
        "gcs_storage_path": "",
        # -- multi-host control plane (reference: GCS server bind address
        # + raylet heartbeats, gcs_health_check_manager.h) ---------------
        # Bind host for the head's daemon listener + transfer server.
        # 127.0.0.1 = single machine; 0.0.0.0 to accept remote hosts.
        "node_host": "127.0.0.1",
        # Fixed head control port (0 = ephemeral).
        "head_port": 0,
        # Sharded selector event loops owning every daemon connection
        # on the head (reads, frame reassembly, writer drains — the
        # reference's GCS asio io_service face). 0 = auto: half the
        # cores, capped at 2 (control traffic is cheap per event; the
        # shards exist for fairness, not throughput).
        "head_event_loops": 0,
        # Daemon heartbeat interval (liveness + load report).
        "node_heartbeat_s": 2.0,
        # Missed heartbeats tolerated before the head declares a node
        # dead even though its TCP connection looks open (half-open
        # links, frozen daemons; reference:
        # gcs_health_check_manager.h failure_threshold). Deliberately
        # generous (15 x 2s = 30s, the reference's classic node-failure
        # window): the head process may stall its routing thread for
        # seconds under GIL-heavy driver work, and a false node death
        # is far costlier than slow detection. 0 disables.
        "node_heartbeat_miss_limit": 15.0,
        # -- pull/reconnect hardening (reference: object manager retries
        # + gcs_rpc_client.h exponential backoff) ------------------------
        # Transient-failure retries per object pull (connect resets,
        # mid-transfer EOF). Exponential backoff with jitter between
        # attempts; ObjectLostError after exhaustion.
        "pull_retry_attempts": 4,
        # Initial retry backoff; doubles per attempt, capped at 2s.
        "pull_retry_backoff_s": 0.1,
        # Overall wall-clock budget for one object pull including all
        # retries; a hung transfer fails typed instead of wedging.
        "pull_deadline_s": 120.0,
        # Pull admission control: concurrent cross-node object pulls
        # (reference: pull_manager.h in-flight bytes cap).
        "pull_max_concurrent": 4,
        # Objects above this split into parallel range-pulls (reference:
        # object_buffer_pool.h chunked transfers); one TCP stream's recv
        # loop caps well under NIC/loopback bandwidth.
        "pull_parallel_threshold_mb": 64.0,
        # Connections per large-object pull (1 = sequential).
        "pull_parallel_streams": 4,
        # Same-host transfers of arena-backed objects ADOPT the source
        # slot in place (zero-copy, cross-process pin through the shared
        # arena header) instead of copying. Disable to force copies.
        "same_host_adoption": True,
        # Same-host copies above this go through the host copy gate:
        # concurrent first-touch of fresh tmpfs pages collapses ~10x on
        # small hosts (kernel shmem allocation contention), so big
        # copies are admission-controlled per host. 0 disables.
        "transfer_serialize_threshold_mb": 64.0,
        # Width of the host copy gate: how many gated copies may run
        # concurrently per host (FIFO admission beyond that). 0 = auto,
        # scaled to the host's cores (1 on 1-2 core boxes — full
        # serialization, the measured optimum there — up to 4 on big
        # hosts whose page-allocation bandwidth one copy can't
        # saturate). netcomm._auto_gate_width.
        "host_copy_gate_width": 0,
        # -- direct worker<->worker call plane (reference: the direct
        # actor transport, core_worker/transport/direct_actor_task_
        # submitter — steady-state actor calls never route through a
        # central process). Falsy => every actor call and nested-result
        # delivery takes the head-routed path unchanged.
        "direct_calls_enabled": True,
        # Broker + connect budget for establishing one direct channel;
        # exhaustion falls back to the head path for that handle.
        "direct_channel_timeout_s": 10.0,
        # Nested-submission result forwarding (head -> submitter
        # RESULT_FWD push replacing the pull round trip). Off => nested
        # gets go through the classic blocking GET_LOCATIONS, while the
        # actor-call fast path stays on.
        "direct_result_forwarding": True,
        # Resolved direct-call result locations cached caller-side
        # (evictable — the head's directory is authoritative once the
        # batched accounting lands).
        "direct_result_cache_size": 8192,
        # After a channel death the (caller, actor) pair is allowed to
        # re-dial once this cooldown elapses (exponential per attempt),
        # up to max_attempts — one transient TCP reset must not cost
        # the pair its fast path for the process lifetime. 0 attempts
        # restores the old permanent pin.
        "direct_redial_backoff_s": 1.0,
        "direct_redial_max_attempts": 3,
        # Callee-side cross-plane merge gate: out-of-order arrivals per
        # caller held until their predecessors execute. Past the cap
        # (or the hold timeout) the oldest held call is force-admitted
        # with a warning — liveness backstop, never the exact path
        # (reference: the actor scheduling queue's bounded reorder
        # wait).
        "direct_seq_reorder_cap": 1024,
        "direct_seq_hold_timeout_s": 30.0,
        # Tasks dispatched onto one (head-local) worker under a single
        # resource grant before completions must drain it (reference:
        # max_tasks_in_flight_per_worker=10, direct task transport
        # pipelining). The worker executes them strictly in order, so
        # the resource contract holds; the grant releases when the
        # pipeline drains. TPU tasks never pipeline (chip exclusivity).
        "max_tasks_in_flight_per_worker": 16,
        # -- serve data plane on the direct call plane (reference: the
        # proxy's replica scheduler submitting via the direct actor
        # transport — steady-state serve requests never touch a central
        # process). Falsy => every proxy request takes the classic
        # head-routed handle path unchanged, and the serve-direct
        # client does zero work (counter-guarded in ci_fast).
        "serve_direct_enabled": True,
        # Request/response bodies above this many serialized bytes move
        # zero-copy through the shared same-node arena (pinned-view
        # reads) instead of being pickled into the channel frame.
        # 0 disables the arena body path (always inline).
        "serve_direct_body_threshold": 64 * 1024,
        # -- direct object transfer plane (reference: the object
        # manager's worker-to-worker pulls, object_manager/object_
        # manager.cc Push/Pull — chunked transfers between the owners'
        # processes, never through a central broker). Falsy => every
        # remote-object read takes the daemon-relayed PULL_OBJECT path
        # unchanged and the transfer client does zero work
        # (counter-guarded in ci_fast).
        "direct_object_transfer_enabled": True,
        # One OBJ_CHUNK frame's payload size. Chunks ride the channel
        # as pickle-5 out-of-band buffers (separate iovecs, no payload
        # pickling); sized to amortize framing without head-of-line
        # blocking actor results behind a multi-second write.
        "direct_transfer_chunk_mb": 8.0,
        # Objects at or below this many bytes skip the channel plane:
        # the daemon round trip is already ~free for small objects and
        # the inline-location path never reaches a pull at all.
        "direct_transfer_min_bytes": 0,
        # Per-worker cap on concurrently SERVED direct pulls; excess
        # requests are refused with a typed busy marker and the caller
        # falls back to the daemon path (admission control so bulk
        # pulls cannot starve the executor serving actor calls).
        "direct_transfer_max_serving": 4,
        # -- streaming shuffle exchange (ISSUE 18: all-to-all on the
        # direct transfer plane, data/shuffle.py) ------------------------
        # Output partition count for streaming shuffles/sorts/groupbys
        # (DataContext.shuffle_partitions seeds from this; the stream's
        # length is unknown so the bulk n=num_blocks heuristic can't
        # apply).
        "shuffle_partitions": 16,
        # CALLER-side cap on concurrent direct pulls to one peer node
        # (per link). A shuffle reduce fans pulls at every producer
        # node at once; without pacing a shard stampede trips the
        # server-side direct_transfer_max_serving admission control and
        # degrades whole shard sets to the daemon relay. Matched to
        # that serving cap by default. 0 disables the gate.
        "shuffle_link_inflight": 4,
        # Max un-merged shard blocks a shuffle reducer buffers before
        # folding the arrived prefix into its accumulator (bounds the
        # reduce merge backlog; concat is associative so folding early
        # is bit-identical to one terminal concat).
        "shuffle_merge_budget": 8,
        # How long a task return blocks for store capacity before the
        # put fails typed. Concurrent reducers on one node each hold an
        # UNSEALED output segment while merging; unsealed bytes cannot
        # spill, so a store smaller than the overlap must wait for a
        # neighbor to seal (then spill) rather than fail the task.
        # 0 disables the wait (puts fail on first full-store miss).
        "put_pressure_deadline_s": 30.0,
        # -- file-store segment recycling (the file-per-object store's
        # answer to the arena's pre-faulted pages: freed segments are
        # renamed into a pool and re-claimed by size-compatible
        # reserves, so hot put loops reuse already-faulted tmpfs pages
        # instead of paying kernel page allocation per put). Pooled
        # bytes stay accounted and are reclaimed before any spill.
        # 0 disables pooling (every free unlinks immediately).
        "store_segment_pool_mb": 512.0,
        # Only segments at least this large are pooled; tiny files
        # gain nothing from page reuse and would churn the pool.
        "store_segment_pool_min_bytes": 1 << 20,
        # -- zero-copy put path (ISSUE 17: serialize directly into the
        # reserved segment). On: put() sizes the payload out-of-band,
        # reserves the segment (striped pool claim, kept-hot mmaps),
        # writes the header in place and NT-copies each buffer exactly
        # once to its final offset. Off: the staging write path
        # (write_to_fd / write_into through the gate) runs unchanged.
        "store_zero_copy_put_enabled": True,
        # Puts below this size never acquire a HostCopyGate ticket,
        # whatever the gate threshold is tuned to: small copies can't
        # meaningfully overlap page-allocation storms, and a ticket
        # round trip would dominate their latency.
        "host_copy_gate_min_bytes": 256 << 10,
        # Stripe count for per-client segment-pool reservation: each
        # writer thread claims from its own stripe of pooled slots
        # (falling back to stealing), so concurrent writers on
        # different segments never serialize on one pool lock.
        "store_put_stripes": 8,
        # Proxy-side admission control: when EVERY replica of a
        # deployment has at least this many proxy-tracked in-flight
        # requests, new requests shed with 503 instead of queueing
        # into a wedged replica pool. 0 disables shedding.
        "serve_max_queue_per_replica": 128,
        # -- hybrid scheduling policy (reference: scheduler_spread_threshold,
        # hybrid_scheduling_policy.cc:48 — prefer the local/preferred node
        # while its critical-resource utilization stays below this, then
        # spread to the least-utilized node) -----------------------------
        "scheduler_spread_threshold": 0.5,
        # Top-k randomization among equally-good spread candidates, as a
        # fraction of alive nodes (reference: kSchedulerTopKFraction).
        "scheduler_top_k_fraction": 0.2,
        # A node whose workers report this many concurrent direct object
        # transfers (summed transfer_inflight gauges) loses its hybrid
        # tiebreak: its link is saturated and co-scheduling data-hungry
        # work onto it serializes both transfers.
        "scheduler_transfer_busy_threshold": 4,
        # Infeasible tasks fail fast by default; an active autoscaler
        # raises this so demand can park while capacity is launched
        # (reference: infeasible queue + autoscaler demand satisfaction).
        "infeasible_task_grace_s": 0.0,
        # -- head fault tolerance (reference: GCS server restart +
        # gcs_client_reconnection_test.cc) -------------------------------
        # Node-daemon reconnect attempts after losing the head (0 = die
        # with the cluster — the in-process test-cluster default;
        # `ray_tpu start --address` join mode raises it so production
        # nodes survive a head restart).
        "head_reconnect_attempts": 0,
        # Initial reconnect backoff; doubles per attempt, capped at 5s.
        "head_reconnect_backoff_s": 0.5,
        # -- graceful node drain (reference: gcs_node_manager DrainNode +
        # autoscaler-v2 drain requests; docs/DRAIN.md) --------------------
        # Budget for one node drain: running tasks finish, serve replicas
        # empty, sole-copy objects re-home. Expiry degrades to the hard
        # node-death path (the pre-drain semantics).
        "drain_deadline_s": 30.0,
        # A node must stay *continuously* idle this long past the
        # autoscaler idle timeout before scale-down picks it — bursty
        # load that goes idle for milliseconds must not flap nodes.
        "scale_down_idle_grace_s": 5.0,
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, Any] = {}
        for name, default in self._DEFAULTS.items():
            var = f"RAY_TPU_{name.upper()}"
            env = os.environ.get(var)
            if env is not None:
                try:
                    self._values[name] = _coerce(env, default)
                    continue
                except (ValueError, TypeError):
                    import warnings
                    warnings.warn(
                        f"Ignoring malformed {var}={env!r} (expected "
                        f"{type(default).__name__}); using default "
                        f"{default!r}.", stacklevel=2)
            self._values[name] = default

    def __getattr__(self, name: str) -> Any:
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError(f"no config entry {name!r}") from None

    def set(self, name: str, value: Any) -> None:
        """Programmatic override (tests)."""
        with self._lock:
            if name not in self._DEFAULTS:
                raise KeyError(f"unknown config entry {name!r}")
            self._values[name] = value

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._values)


ray_config = RayConfig()
