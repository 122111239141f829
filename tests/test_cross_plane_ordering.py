"""Cross-plane call sequencing + direct streaming generators.

Tentpole contract (reference: direct_actor_task_submitter sequence
numbers + the actor scheduling queue's out-of-order handling): every
actor call a worker submits is stamped with a per-(caller, actor)
sequence number on BOTH planes, and the callee-side merge gate
(worker_proc.SequenceGate) replays EXACT submission order no matter
which transport carried each call — a head-routed call (streaming,
retry_exceptions, warm-up transient) can no longer be overtaken by a
later direct call. Streaming generators ride the brokered channel
(GEN_ITEM callee->caller; head accounting only at terminal
registration), channel death mid-stream yields a typed error with the
arrived prefix intact, and a channel death no longer pins the pair to
the head path forever (re-dial after backoff).

The whole module runs under the runtime lock-order tracker (conftest
guard): any potential ABBA cycle recorded by the new gate/stream locks
fails the test.
"""

import multiprocessing
import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu._private.config import ray_config


@pytest.fixture(autouse=True)
def _force_direct_plane():
    """This module exercises the direct plane itself: force it on even
    under the flag-off acceptance sweep (same contract as
    test_direct_calls)."""
    prev_env = os.environ.pop("RAY_TPU_DIRECT_CALLS_ENABLED", None)
    prev_cfg = ray_config.direct_calls_enabled
    ray_config.set("direct_calls_enabled", True)
    yield
    ray_config.set("direct_calls_enabled", prev_cfg)
    if prev_env is not None:
        os.environ["RAY_TPU_DIRECT_CALLS_ENABLED"] = prev_env


@pytest.fixture
def fresh():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class SeqLog:
    """max_concurrency=1 callee persisting its observed execution order
    to a file so the record survives SIGKILL + restart."""

    def __init__(self, path):
        self.path = path

    def _mark(self, i):
        with open(self.path, "a") as f:
            f.write(f"{os.getpid()} {i}\n")

    def add(self, i):
        self._mark(i)
        return i

    def gen3(self, i):
        self._mark(i)
        for k in range(3):
            yield (i, k)

    def slow_gen(self, i, n, delay):
        self._mark(i)
        for k in range(n):
            time.sleep(delay)
            yield (i, k)

    def pid(self):
        return os.getpid()


@ray_tpu.remote
class Caller:
    """Worker-side caller interleaving the three call shapes — plain
    (direct channel), retry_exceptions and streaming (head-routed or
    channel) — against one sequential callee."""

    def __init__(self, callee):
        self.callee = callee

    def run_mixed(self, n, retries=2):
        refs, gens = [], []
        for i in range(n):
            kind = i % 9
            if kind == 2:
                gens.append((i, self.callee.gen3.options(
                    num_returns="streaming",
                    max_task_retries=retries).remote(i)))
            elif kind == 5:
                refs.append((i, self.callee.add.options(
                    retry_exceptions=True,
                    max_task_retries=retries).remote(i)))
            else:
                refs.append((i, self.callee.add.options(
                    max_task_retries=retries).remote(i)))
        results = {}
        for i, r in refs:
            try:
                results[i] = ray_tpu.get(r, timeout=90)
            except Exception as e:
                results[i] = f"err:{type(e).__name__}"
        streams = {}
        for i, g in gens:
            items = []
            try:
                for ref in g:
                    items.append(ray_tpu.get(ref, timeout=90))
            except Exception as e:
                items.append(f"err:{type(e).__name__}")
            streams[i] = items
        return results, streams

    def consume_stream(self, n):
        out = []
        for ref in self.callee.gen3.options(
                num_returns="streaming").remote(n):
            out.append(ray_tpu.get(ref, timeout=60))
        return out

    def start_slow_stream(self, i, n, delay):
        self._gen = self.callee.slow_gen.options(
            num_returns="streaming").remote(i, n, delay)
        return True

    def finish_slow_stream(self):
        items, err = [], None
        try:
            for ref in self._gen:
                items.append(ray_tpu.get(ref, timeout=60))
        except Exception as e:
            err = type(e).__name__ + ": " + str(e)[:80]
        return items, err

    def channel_state(self):
        from ray_tpu._private import direct, state
        plane = state._worker.direct
        live = fall = 0
        for v in plane._chans.values():
            if isinstance(v, direct._Fallback):
                fall += 1
            else:
                live += 1
        return live, fall


def _assert_order(path, completed_ids):
    """The callee-side record must show, per incarnation, a strictly
    increasing subsequence of submission order, jointly covering every
    completed call at least once."""
    per_pid = {}
    seen_order = []
    with open(path) as f:
        for line in f:
            pid_s, i_s = line.split()
            per_pid.setdefault(int(pid_s), []).append(int(i_s))
            seen_order.append(int(i_s))
    for pid, seq in per_pid.items():
        # A retried call re-executes AFTER the restart boundary, in its
        # requeued (seq-ordered) position — within one incarnation the
        # observed order must be exactly increasing.
        assert seq == sorted(seq), (
            f"per-caller submission order violated on incarnation "
            f"{pid}: {seq}")
        assert len(set(seq)) == len(seq), (
            f"duplicate execution within one incarnation {pid}: {seq}")
    executed = set(seen_order)
    missing = set(completed_ids) - executed
    assert not missing, f"completed calls never observed callee-side: " \
                        f"{sorted(missing)}"
    return per_pid


def test_mixed_plane_order_exact(fresh, tmp_path):
    """No faults: streaming + retry_exceptions + plain interleaved from
    one worker caller execute in exact submission order."""
    log = SeqLog.options(max_task_retries=0).remote(
        str(tmp_path / "order.log"))
    caller = Caller.remote(log)
    results, streams = ray_tpu.get(caller.run_mixed.remote(90),
                                   timeout=120)
    assert all(results[i] == i for i in results), results
    for i, items in streams.items():
        assert items == [(i, k) for k in range(3)], (i, items)
    per_pid = _assert_order(str(tmp_path / "order.log"), range(90))
    # One incarnation, so the exactness claim is the strongest form:
    # the full interleaved sequence equals submission order.
    (seq,) = per_pid.values()
    assert seq == list(range(90))


def test_worker_stream_matches_head_path(fresh):
    """Channel-streamed results are byte-identical to the head-routed
    stream of the same generator (the driver consumes head-path)."""
    @ray_tpu.remote
    class G:
        def stream(self, n):
            for i in range(n):
                yield {"i": i, "blob": b"v" * (i * 1000)}

    g = G.remote()

    @ray_tpu.remote
    class C:
        def __init__(self, g):
            self.g = g

        def consume(self, n):
            return [ray_tpu.get(r) for r in self.g.stream.options(
                num_returns="streaming").remote(n)]

    c = C.remote(g)
    via_channel = ray_tpu.get(c.consume.remote(8), timeout=60)
    via_head = [ray_tpu.get(r) for r in g.stream.options(
        num_returns="streaming").remote(8)]
    assert via_channel == via_head


def test_stream_channel_death_mid_stream(fresh):
    """SIGKILL the callee mid-stream: the arrived prefix stays readable
    in order, then a typed ActorDiedError surfaces (streams never
    retry — head-path semantics)."""
    log = SeqLog.remote("/dev/null")
    caller = Caller.remote(log)
    pid = ray_tpu.get(log.pid.remote())
    assert ray_tpu.get(caller.start_slow_stream.remote(0, 50, 0.1),
                       timeout=30)
    time.sleep(1.2)  # a few items have streamed
    os.kill(pid, signal.SIGKILL)
    items, err = ray_tpu.get(caller.finish_slow_stream.remote(),
                             timeout=60)
    assert err is not None and "ActorDied" in err, (items, err)
    # No lost or duplicated items: the arrived prefix is exact.
    assert items == [(0, k) for k in range(len(items))], items


def test_redial_after_channel_death():
    """A channel death must not pin the pair to the head path forever:
    after the backoff cooldown the caller re-dials the restarted
    incarnation and the fast path returns."""
    prev = ray_config.direct_redial_backoff_s
    ray_config.set("direct_redial_backoff_s", 0.2)
    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        class Echo:
            def echo(self, x):
                return x

            def pid(self):
                return os.getpid()

        callee = Echo.options(max_restarts=1).remote()
        pid = ray_tpu.get(callee.pid.remote())

        @ray_tpu.remote
        class Drv:
            def __init__(self, c):
                self.c = c

            def call(self, x):
                return ray_tpu.get(self.c.echo.options(
                    max_task_retries=2).remote(x), timeout=60)

            def chans(self):
                from ray_tpu._private import direct, state
                plane = state._worker.direct
                live = fall = 0
                for v in plane._chans.values():
                    if isinstance(v, direct._Fallback):
                        fall += 1
                    else:
                        live += 1
                return live, fall

        d = Drv.remote(callee)
        assert ray_tpu.get(d.call.remote(1)) == 1
        assert ray_tpu.get(d.chans.remote()) == (1, 0)
        os.kill(pid, signal.SIGKILL)
        # The in-flight-free channel EOF pins the pair transiently; the
        # next calls (after restart + cooldown) must re-dial.
        deadline = time.monotonic() + 30
        live = fall = None
        while time.monotonic() < deadline:
            assert ray_tpu.get(d.call.remote(2), timeout=60) == 2
            live, fall = ray_tpu.get(d.chans.remote())
            if live == 1 and fall == 0:
                break
            time.sleep(0.3)
        assert (live, fall) == (1, 0), (
            f"pair never re-dialed after channel death: live={live} "
            f"fallback={fall}")
    finally:
        ray_tpu.shutdown()
        ray_config.set("direct_redial_backoff_s", prev)


def test_direct_done_emits_submission_events():
    """Satellite: DIRECT_DONE accounting entries produce head-side
    SUBMITTED + terminal events, so state.list_tasks rows for direct
    calls carry submission-side state like head-path calls."""
    prev = os.environ.get("RAY_TPU_TELEMETRY")
    os.environ["RAY_TPU_TELEMETRY"] = "1"
    from ray_tpu._private import telemetry
    was_enabled = telemetry.enabled
    telemetry.configure(True)
    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        class Echo:
            def tagged_echo(self, x):
                return x

        @ray_tpu.remote
        class Drv:
            def __init__(self, c):
                self.c = c

            def run(self, n):
                return ray_tpu.get(
                    [self.c.tagged_echo.remote(i) for i in range(n)])

        callee = Echo.remote()
        d = Drv.remote(callee)
        assert ray_tpu.get(d.run.remote(20), timeout=60) == list(range(20))
        # Force the buffered events to land: the caller's SUBMITTED
        # batch drains with its own completion; a head-routed call to
        # the callee drains ITS buffered FINISHED events (direct
        # completions have no head frame to piggyback on).
        assert ray_tpu.get(callee.tagged_echo.remote(99),
                           timeout=60) == 99
        assert ray_tpu.get(d.run.remote(1), timeout=60) == [0]
        from ray_tpu._private import state
        node = state.get_node()
        deadline = time.monotonic() + 10
        states = set()
        while time.monotonic() < deadline:
            states = {e.get("state") for e in node.gcs.telemetry.events()
                      if "tagged_echo" in (e.get("name") or "")}
            if "SUBMITTED" in states and (
                    "FINISHED" in states or "FAILED" in states):
                break
            time.sleep(0.2)
        assert "SUBMITTED" in states, states
        assert "FINISHED" in states, states
        rows = [r for r in __import__(
            "ray_tpu.util.state", fromlist=["list_tasks"]).list_tasks()
            if "tagged_echo" in (r.get("name") or "")]
        assert rows and all(r.get("state") for r in rows), rows
    finally:
        ray_tpu.shutdown()
        # As it was found: left off, every later test file of this
        # process runs without telemetry (tests/test_scale_sim.py then
        # reads no head gauges).
        telemetry.configure(was_enabled)
        if prev is None:
            os.environ.pop("RAY_TPU_TELEMETRY", None)
        else:
            os.environ["RAY_TPU_TELEMETRY"] = prev


def test_channel_stream_consumable_beyond_submitter(fresh):
    """A channel-stream generator handle returned to the DRIVER must
    resolve there: the terminal accounting entry closes the head's
    stream state (review fix — it used to hang on an empty stream),
    and SHM-backed items register with lineage like head-path
    GEN_ITEMs."""
    @ray_tpu.remote
    class G:
        def stream(self, n):
            for i in range(n):
                yield b"x" * (300 * 1024)  # SHM-backed items

    @ray_tpu.remote
    class C:
        def __init__(self, g):
            self.g = g

        def start(self, n):
            gen = self.g.stream.options(
                num_returns="streaming").remote(n)
            # Consume fully worker-side (terminal entry ships with the
            # item registrations + head-side stream closure), then hand
            # the generator handle to the driver. (Returning an
            # UNCONSUMED generator abandons it at local GC — the
            # release-on-del semantics both planes share.)
            items = [ray_tpu.get(r) for r in gen]
            assert len(items) == n
            return gen

    g = G.remote()
    c = C.remote(g)
    gen = ray_tpu.get(c.start.remote(3), timeout=60)
    # Driver-side foreign consumption: re-read from the start (the
    # pickled handle carries the worker's consumed index) — must
    # terminate via the head's closed stream state, not hang.
    gen._index = 0
    gen._released = True  # the submitting worker already released
    out = []
    for ref in gen:
        out.append(len(ray_tpu.get(ref, timeout=30)))
    assert out == [300 * 1024] * 3
    # SHM items carry lineage (reconstructable after node loss).
    from ray_tpu._private import state
    from ray_tpu._private.ids import object_id_for_return
    node = state.get_node()
    entry = node.gcs.objects.entry(
        object_id_for_return(gen._task_id, 0))
    assert entry is not None and entry.lineage is not None, \
        "channel-stream SHM item registered without lineage"


def test_sequence_gate_unit():
    """Gate semantics in isolation: cross-plane holds, drain order,
    settlement release, replay pass-through, overflow backstop."""
    from ray_tpu._private.worker_proc import SequenceGate

    class _W:
        _actor_spec = None

        class client:
            @staticmethod
            def gcs_request(*a, **k):
                return []

    gate = SequenceGate(_W())
    ran = []

    def mk(spec_seq, preds):
        class S:
            caller_id = b"c1"
            caller_seq = spec_seq
            seq_preds = tuple(preds)
        return S()

    # Direct seq 1 arrives before head seq 0 (its pred): held.
    gate.admit(mk(1, (0,)), lambda: ran.append(1))
    assert ran == []
    gate.admit(mk(0, ()), lambda: ran.append(0))
    assert ran == [0, 1]
    # Replay of an executed slot runs immediately (retry semantics).
    gate.admit(mk(0, ()), lambda: ran.append("r0"))
    assert ran[-1] == "r0"
    # Settlement releases a hold whose pred will never arrive.
    gate.admit(mk(3, (2,)), lambda: ran.append(3))
    assert 3 not in ran
    gate.on_settled(b"c1", [2])
    assert ran[-1] == 3
    # Older-held rule: a later admissible seq must wait behind an
    # earlier held one from the same caller.
    gate.admit(mk(5, (4,)), lambda: ran.append(5))
    gate.admit(mk(6, ()), lambda: ran.append(6))
    assert 5 not in ran and 6 not in ran
    gate.on_settled(b"c1", [4])
    assert ran[-2:] == [5, 6]
    # all_=True (dead caller) flushes every hold in seq order.
    gate.admit(mk(8, (7,)), lambda: ran.append(8))
    gate.admit(mk(9, (7,)), lambda: ran.append(9))
    gate.on_settled(b"c1", None, all_=True)
    assert ran[-2:] == [8, 9]


def test_burst_split_preserves_order():
    """admit_burst: a held slot mid-burst splits the lean batch; the
    drained cross-plane slot interleaves at its seq position."""
    from ray_tpu._private.worker_proc import SequenceGate

    class _W:
        _actor_spec = None

    gate = SequenceGate(_W())
    ran = []

    def batch_runner(specs):
        ran.extend(s.caller_seq for s in specs)

    def mk(seq, preds):
        class S:
            caller_id = b"c1"
            caller_seq = seq
            seq_preds = tuple(preds)
        return S()

    # Burst [0, 1, 3(pred 2), 4]: 0,1 run; 3 holds; 4 holds behind 3.
    gate.admit_burst([mk(0, ()), mk(1, ()), mk(3, (2,)), mk(4, ())],
                     batch_runner)
    assert ran == [0, 1]
    # Head arrival 2 admits, then drains 3 and 4 in order.
    gate.admit(mk(2, ()), lambda: ran.append(2))
    assert ran == [0, 1, 2, 3, 4]


def _cpu_burner(stop_path):
    while not os.path.exists(stop_path):
        sum(i * i for i in range(10000))


@pytest.mark.chaos
@pytest.mark.slow
def test_mixed_plane_ordering_chaos(tmp_path):
    """THE acceptance chaos run: interleaved streaming /
    retry_exceptions / plain calls to one max_concurrency=1 actor
    under seeded direct.connect drops plus a SIGKILL + restart,
    20/20 seeds under full-core background load — exact per-caller
    order observed callee-side on every incarnation, no lost or
    duplicated stream items, typed errors only where the budget ran
    out. Runs under lockdep via the conftest guard."""
    stop_path = str(tmp_path / "stop_burn")
    burners = [multiprocessing.Process(target=_cpu_burner,
                                       args=(stop_path,), daemon=True)
               for _ in range(os.cpu_count() or 2)]
    for b in burners:
        b.start()
    try:
        for round_no, seed in enumerate(range(40, 60)):
            kill = round_no % 2 == 1  # alternate: drops only / drops+kill
            path = str(tmp_path / f"order_{seed}.log")
            ray_tpu.init(num_cpus=4, fault_config={
                "seed": seed,
                "rules": [{"site": "direct.connect", "action": "drop",
                           "prob": 0.4}]})
            try:
                log = SeqLog.options(max_restarts=1).remote(path)
                caller = Caller.remote(log)
                pid = ray_tpu.get(log.pid.remote(), timeout=30)
                fut = caller.run_mixed.remote(72)
                if kill:
                    time.sleep(0.6)
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                results, streams = ray_tpu.get(fut, timeout=180)
                completed = [i for i, v in results.items()
                             if not str(v).startswith("err")]
                # Retry budget (2) covers one SIGKILL: plain and
                # retry_exceptions calls must all complete.
                assert len(completed) == len(results), {
                    i: v for i, v in results.items()
                    if str(v).startswith("err")}
                assert all(results[i] == i for i in completed)
                for i, items in streams.items():
                    body = [it for it in items
                            if not isinstance(it, str)]
                    # No lost/duplicated items: an exact prefix,
                    # complete unless the stream died with the callee.
                    assert body == [(i, k) for k in range(len(body))], \
                        (i, items)
                    if not (items and isinstance(items[-1], str)):
                        assert len(body) == 3, (i, items)
                _assert_order(path, completed)
            finally:
                ray_tpu.shutdown()
    finally:
        with open(stop_path, "w") as f:
            f.write("x")
        for b in burners:
            b.join(timeout=5)
