"""A training run records its own start (PR 33): the run's spans from
`ray_tpu.init` and `fit()` to the first report (util/tracing.py Run), the
log of every program a worker builds (util/profiling.py compile_log), the
file the controller writes from both (`<experiment dir>/run_timeline.json`)
and, imported as the rehearsals import the harness, the benchmark's nine
readers of that file on the timeline recorded on the chip.

Everything here runs on the CPU: what is checked is structure, order and
clocks, never a time."""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.train import (JaxBackendConfig, JaxTrainer, RunConfig,
                           ScalingConfig, session)
from ray_tpu.train.worker_group import TrainWorker
from ray_tpu.util import profiling, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# span -> its parent, as ISSUE 33's table has them
PARENTS = {
    "ray_tpu.init": None,
    "ray_tpu.train.fit": None,
    "ray_tpu.train.start_group": "ray_tpu.train.fit",
    "ray_tpu.train.worker_setup": "ray_tpu.train.start_group",
    "ray_tpu.train.backend_start": "ray_tpu.train.fit",
    "ray_tpu.train.loop": "ray_tpu.train.fit",
    "ray_tpu.train.first_report": "ray_tpu.train.loop",
}
CLOCK_ERROR_S = 0.005    # two processes of one machine on time.time()


def _loop(c):
    """train_loop_per_worker: builds one jitted function of its own,
    reports, and then does what the case asks."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train

    if c.get("cache_dir"):
        jax.config.update("jax_compilation_cache_dir", c["cache_dir"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    @jax.jit
    def timeline_step(x):
        return jnp.tanh(x) * 2 + 1

    y = jax.block_until_ready(timeline_step(jnp.arange(8.0)))
    train.report({"loss": float(y[0])})
    if c.get("wait_for"):
        # What a job killed now would leave behind: the controller writes
        # the file at the first report, while the loop still runs.
        deadline = time.time() + 30
        while not os.path.exists(c["wait_for"]) and time.time() < deadline:
            time.sleep(0.02)
        with open(c["wait_for"]) as f:
            train.report({"early": json.load(f)})
    if c.get("boom"):
        raise ValueError("boom")


def _fit(storage, name="job", **config):
    result = JaxTrainer(
        _loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name=name, storage_path=str(storage))).fit()
    with open(os.path.join(result.path, "run_timeline.json")) as f:
        return result, json.load(f)


@pytest.fixture(scope="module")
def job(ray_start_shared, tmp_path_factory):
    """(Result, run_timeline.json) of one one-worker CPU job."""
    storage = tmp_path_factory.mktemp("run")
    return _fit(storage, wait_for=os.path.join(
        str(storage), "job", "run_timeline.json"))


def _by_name(spans):
    out = {}
    for s in spans:
        assert s["name"] not in out, f"two spans named {s['name']}"
        out[s["name"]] = s
    return out


def test_spans_form_one_tree_under_fit(job):
    result, doc = job
    assert result.error is None and set(doc) == {"trace_id", "spans",
                                                 "workers"}
    spans = _by_name(doc["spans"])
    # a CPU job takes every span of the table but the runtime's start
    assert set(spans) == set(PARENTS) - {"ray_tpu.train.backend_start"}
    for name, s in spans.items():
        assert set(s) >= {"name", "trace_id", "span_id", "parent_span_id",
                          "start", "end", "attributes", "error"}
        assert s["trace_id"] == doc["trace_id"] and s["error"] is None
        assert s["start"] <= s["end"], name
        parent = PARENTS[name]
        assert s["parent_span_id"] == (parent and spans[parent]["span_id"])
        if parent:      # children inside parents, to within clock error
            assert spans[parent]["start"] - CLOCK_ERROR_S <= s["start"]
            assert s["end"] <= spans[parent]["end"] + CLOCK_ERROR_S
    assert spans["ray_tpu.init"]["end"] <= \
        spans["ray_tpu.train.fit"]["start"]
    assert spans["ray_tpu.train.fit"]["attributes"] == {
        "name": "job", "num_workers": 1,
        "resources_per_worker": {"CPU": 1.0}}
    assert spans["ray_tpu.train.loop"]["attributes"] == {"rank": 0}
    mark = spans["ray_tpu.train.first_report"]
    assert mark["end"] - mark["start"] < 0.01

    tree = tracing.build_trace(doc["spans"])
    assert [r["name"] for r in tree["roots"]] == ["ray_tpu.init",
                                                  "ray_tpu.train.fit"]
    assert tree["roots"][0]["children"] == []
    assert tree["span_count"] == len(doc["spans"])
    assert [s["name"] for s in tree["critical_path"]] == [
        "ray_tpu.train.fit", "ray_tpu.train.loop",
        "ray_tpu.train.first_report"]
    text = tracing.format_trace(tree)
    assert "    ray_tpu.train.loop" in text and "@ rank0]" in text
    assert doc["workers"]["0"]["pid"] != os.getpid()
    assert doc["workers"]["0"]["dropped"] == 0


def test_a_job_killed_after_its_first_report_has_a_file(job):
    """The file the loop found while it was still running: `fit` and
    `loop` open, the mark in, and the tools read it."""
    early = job[0].metrics["early"]
    spans = _by_name(early["spans"])
    assert early["trace_id"] == job[1]["trace_id"]
    assert spans["ray_tpu.train.fit"]["end"] is None
    assert spans["ray_tpu.train.loop"]["end"] is None
    assert spans["ray_tpu.train.start_group"]["end"] is not None
    assert "ray_tpu.train.first_report" in spans
    assert any("timeline_step" in e["fun"]
               for e in early["workers"]["0"]["compile_log"])
    tree = tracing.build_trace(early["spans"])
    assert [s["name"] for s in tree["critical_path"]][:2] == [
        "ray_tpu.train.fit", "ray_tpu.train.loop"]
    assert "ray_tpu.train.loop  [open @ rank0]" in tracing.format_trace(tree)


def _entries_of(doc, fun="timeline_step"):
    return [e for e in doc["workers"]["0"]["compile_log"]
            if fun in e["fun"]]


def test_compile_log_names_the_loops_function_phase_by_phase(job):
    log = job[1]["workers"]["0"]["compile_log"]
    assert all(set(e) == {"fun", "phase", "start", "end", "cache"}
               and e["start"] <= e["end"] for e in log)
    mine = _entries_of(job[1])
    assert [e["phase"] for e in mine] == ["trace", "lower", "compile"]
    assert [e["fun"] for e in mine] == [
        "timeline_step", "jit(timeline_step)", "jit(timeline_step)"]
    assert all(a["end"] <= b["start"] for a, b in zip(mine, mine[1:]))
    loop = _by_name(job[1]["spans"])["ray_tpu.train.loop"]
    assert loop["start"] <= mine[0]["start"] and mine[-1]["end"] <= loop["end"]
    # no persistent cache in this job: nothing asked, nothing answered
    assert {e["cache"] for e in log} == {None}


def test_a_warm_persistent_cache_reads_hit(ray_start_shared, tmp_path):
    cache = str(tmp_path / "cache")
    answers = []
    for run in ("cold", "warm"):
        _, doc = _fit(tmp_path, name=run, cache_dir=cache)
        compiled = [e for e in _entries_of(doc) if e["phase"] == "compile"]
        assert len(compiled) == 1
        answers.append(compiled[0]["cache"])
        assert all(e["cache"] is None for e in _entries_of(doc)
                   if e["phase"] != "compile")
    assert answers == ["miss", "hit"]


def test_a_loop_that_raises_still_leaves_a_file(ray_start_shared, tmp_path):
    result, doc = _fit(tmp_path, boom=True)
    assert "boom" in repr(result.error)
    spans = _by_name(doc["spans"])
    assert "ValueError('boom')" in spans["ray_tpu.train.loop"]["error"]
    assert spans["ray_tpu.train.loop"]["end"] is not None
    # fit() returned its Result: the failure is the loop's
    assert spans["ray_tpu.train.fit"]["error"] is None
    assert "ray_tpu.train.first_report" in spans and _entries_of(doc)
    assert "ERROR" in tracing.format_trace(tracing.build_trace(doc["spans"]))


def test_tracing_on_writes_the_same_spans_and_the_store_answers(
        job, tmp_path):
    was = tracing.enabled
    tracing.enable()
    try:
        _, doc = _fit(tmp_path)
        stored = {s["name"] for s in tracing.get_spans(doc["trace_id"])}
    finally:
        if not was:
            tracing.disable()
    assert sorted(s["name"] for s in doc["spans"]) == sorted(
        s["name"] for s in job[1]["spans"])
    # the usual flush took the run's spans to the head's store as well
    assert stored >= {"ray_tpu.train.fit", "ray_tpu.train.start_group"}
    assert not tracing.get_spans(job[1]["trace_id"])


CHIPS = [  # TPU_VISIBLE_CHIPS, world size, init_distributed -> span taken
    pytest.param("0", 1, True, True, id="one-worker-with-a-chip"),
    pytest.param("0,1,2,3", 1, False, True, id="one-worker-with-four"),
    pytest.param("", 1, True, False, id="cpu-worker"),
    pytest.param("0", 2, False, False, id="replicas-that-may-still-join"),
]


@pytest.mark.parametrize("chips, world, joined, taken", CHIPS)
def test_backend_start_is_taken_only_where_the_runtime_is_settled(
        monkeypatch, chips, world, joined, taken):
    """In this process, as if the worker had been handed `chips`: the span
    closes before the loop's first line runs, and is not taken where the
    loop could still configure jax."""
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", chips)
    first_line = []
    worker = TrainWorker._cls()
    try:
        worker.setup(session.TrainContext(world_size=world),
                     JaxBackendConfig(init_distributed=joined), None,
                     run_trace={"trace_id": "t" * 32, "fit": "f" * 16,
                                "start_group": "g" * 16})
        worker.run(lambda: first_line.append(time.time()), None)
        timeline = worker.poll()["timeline"]
    finally:
        session._set_session(None)
    spans = _by_name(timeline["spans"])
    assert timeline["pid"] == os.getpid()
    assert {s["trace_id"] for s in spans.values()} == {"t" * 32}
    assert spans["ray_tpu.train.worker_setup"]["parent_span_id"] == "g" * 16
    assert spans["ray_tpu.train.loop"]["parent_span_id"] == "f" * 16
    assert ("ray_tpu.train.backend_start" in spans) == taken
    assert "ray_tpu.train.first_report" not in spans    # it never reported
    if taken:
        start = spans["ray_tpu.train.backend_start"]
        assert start["parent_span_id"] == "f" * 16
        assert start["end"] <= spans["ray_tpu.train.loop"]["start"] \
            <= first_line[0]
        assert start["attributes"] == {
            "rank": 0, "platform": "cpu",
            "device_kind": jax.local_devices()[0].device_kind,
            "local_devices": jax.local_device_count()}


def test_a_backend_that_is_not_jax_imports_and_logs_nothing(monkeypatch):
    from ray_tpu.train import BackendConfig

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    worker = TrainWorker._cls()
    try:
        worker.setup(session.TrainContext(), BackendConfig(), None)
        worker.run(lambda: None, None)
        names = [s["name"] for s in worker.poll()["timeline"]["spans"]]
    finally:
        session._set_session(None)
    assert names == ["ray_tpu.train.worker_setup", "ray_tpu.train.loop"]


def _xplane_span(xplane, name):
    """(start, end) in unix seconds of the one host span `name`."""
    zero = profiling.profile_start_unix_ns(xplane)
    found = [(zero + e.start_ns, zero + e.start_ns + e.duration_ns)
             for plane in jax.profiler.ProfileData.from_file(xplane).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events if e.name == name]
    assert len(found) == 1, found
    return found[0][0] / 1e9, found[0][1] / 1e9


def test_log_and_run_spans_lie_on_the_capture_clock(tmp_path):
    """A program built inside a run span, under a capture: the log's
    interval, on time.time(), lies inside the span as the profile holds
    it, on the trace's own clock: one clock, no bridge."""
    run = tracing.Run()

    @jax.jit
    def built_under_capture(x):
        return jnp.sin(x) + 3

    with profiling.capture(str(tmp_path)) as cap:
        with run.span("ray_tpu.train.loop", rank=0) as span:
            jax.block_until_ready(built_under_capture(jnp.ones(4)))
    mine = [e for e in profiling.compile_log()
            if "built_under_capture" in e["fun"]]
    assert [e["phase"] for e in mine] == ["trace", "lower", "compile"]
    start, end = _xplane_span(cap.xplane, "ray_tpu.train.loop")
    assert abs(start - span["start"]) < 1e-3 and abs(end - span["end"]) < 1e-3
    assert cap.start_unix_ns / 1e9 <= start
    assert start - 1e-3 <= mine[0]["start"] and mine[-1]["end"] <= end + 1e-3


SPAN = "/jax/core/compile/{}_duration".format


def test_the_log_is_bounded_and_counts_what_it_dropped():
    log = profiling.CompileLog(keep=4)
    for i in range(6):
        log.on_time_span(SPAN("backend_compile"), float(i), i + 0.5,
                         fun_name=f"jit(f{i})")
    log.on_time_span("/jax/some/other_duration", 0.0, 1.0, fun_name="x")
    assert [e["fun"] for e in log.entries()] == [
        f"jit(f{i})" for i in range(2, 6)]
    assert (log.dropped, log.programs_built) == (2, 6)
    assert log.entries()[0] == {"fun": "jit(f2)", "phase": "compile",
                                "start": 2.0, "end": 2.5, "cache": None}


def test_the_log_folds_what_began_inside_an_entry_into_it():
    log = profiling.CompileLog()
    # jnp's own functions traced inside f's trace, a kernel's body inside
    # its lowering; an eager program built while g is traced stays
    log.on_time_span(SPAN("jaxpr_trace"), 1.1, 1.2, fun_name="add")
    log.on_time_span(SPAN("jaxpr_trace"), 1.3, 1.4, fun_name="multiply")
    log.on_time_span(SPAN("jaxpr_trace"), 1.0, 2.0, fun_name="f")
    log.on_time_span(SPAN("jaxpr_trace"), 2.2, 2.3, fun_name="body")
    log.on_time_span(SPAN("jaxpr_to_mlir_module"), 2.1, 3.0,
                     fun_name="jit(f)")
    log.on_time_span(SPAN("backend_compile"), 3.0, 9.0, fun_name="jit(f)")
    log.on_time_span(SPAN("jaxpr_trace"), 10.1, 10.2, fun_name="iota")
    log.on_time_span(SPAN("backend_compile"), 10.3, 10.4,
                     fun_name="jit(iota)")
    log.on_time_span(SPAN("jaxpr_trace"), 10.0, 11.0, fun_name="g")
    assert [(e["fun"], e["phase"]) for e in log.entries()] == [
        ("f", "trace"), ("jit(f)", "lower"), ("jit(f)", "compile"),
        ("iota", "trace"), ("jit(iota)", "compile"), ("g", "trace")]
    assert log.programs_built == 2 and log.dropped == 0


def test_no_number_of_nested_traces_pushes_an_earlier_program_out():
    """PR 42's case: 73 programs built, then one train step whose trace
    holds 5,083 jitted functions of its own (jax.numpy's, the kernels'
    bodies). The log kept 4,096 entries, newest first, so the nested ones
    pushed 73 programs out before the outer trace ended and swallowed
    them, and `programs_built` / `trace_lower_s` read 1 / 30.9 for a run
    whose truth was 74 / 76.5. What a thread has logged since its last
    `compile` now has room of its own: a run of nested entries gives up
    its own oldest, and the outer entry takes those with it uncounted."""
    log = profiling.CompileLog()
    for i in range(73):
        t = 10.0 * i
        log.on_time_span(SPAN("jaxpr_trace"), t, t + 1, fun_name=f"f{i}")
        log.on_time_span(SPAN("jaxpr_to_mlir_module"), t + 1, t + 2,
                         fun_name=f"jit(f{i})")
        log.on_time_span(SPAN("backend_compile"), t + 2, t + 9,
                         fun_name=f"jit(f{i})")
    for i in range(5083):
        log.on_time_span(SPAN("jaxpr_trace"), 1000.0 + i, 1000.5 + i,
                         fun_name="multiply")
    assert len(log.entries()) == 3 * 73 + 4096 and log.dropped == 0
    log.on_time_span(SPAN("jaxpr_trace"), 999.0, 7000.0,
                     fun_name="train_step")
    log.on_time_span(SPAN("jaxpr_to_mlir_module"), 7000.0, 7030.0,
                     fun_name="jit(train_step)")
    log.on_time_span(SPAN("backend_compile"), 7030.0, 7100.0,
                     fun_name="jit(train_step)")
    entries = log.entries()
    assert [e["fun"] for e in entries if e["phase"] == "compile"] == [
        f"jit(f{i})" for i in range(73)] + ["jit(train_step)"]
    assert len(entries) == 3 * 74 and log.programs_built == 74
    assert log.dropped == 0
    assert [e["end"] for e in entries] == sorted(e["end"] for e in entries)
    # entries that do stand and do not fit are dropped, oldest first, and
    # counted: 5,000 traces that no later one swallows, then a compile
    log = profiling.CompileLog(keep=100)
    log.on_time_span(SPAN("backend_compile"), 0.0, 1.0, fun_name="jit(a)")
    for i in range(5000):
        log.on_time_span(SPAN("jaxpr_trace"), 10.0 + i, 10.5 + i,
                         fun_name="eval_shape")
    log.on_time_span(SPAN("backend_compile"), 9000.0, 9001.0,
                     fun_name="jit(b)")
    assert [e["fun"] for e in log.entries()] == ["eval_shape"] * 99 + [
        "jit(b)"]
    assert log.dropped == 4902 and log.programs_built == 2


def test_one_threads_open_run_costs_another_thread_nothing():
    """A thread inside a long trace holds a full run of nested entries
    while another thread builds a program: the other's entries are all
    kept (the log once dropped the entry just logged where everything
    it held was another thread's open run), a program built before
    either is not pushed out, and a run whose thread has gone stands
    and is no longer held under the thread."""
    log = profiling.CompileLog(keep=8)
    log.on_time_span(SPAN("backend_compile"), 0.0, 1.0, fun_name="jit(a)")
    ready, go = threading.Event(), threading.Event()

    def tracing():
        for i in range(20):                 # nested in a trace still open
            log.on_time_span(SPAN("jaxpr_trace"), 10.0 + i, 10.5 + i,
                             fun_name="multiply")
        ready.set()
        go.wait(10)

    other = threading.Thread(target=tracing)
    other.start()
    assert ready.wait(10)
    log.on_time_span(SPAN("jaxpr_trace"), 50.0, 51.0, fun_name="mine")
    assert [e["fun"] for e in log.entries()] == [
        "jit(a)"] + ["multiply"] * 8 + ["mine"]
    log.on_time_span(SPAN("jaxpr_to_mlir_module"), 51.0, 52.0,
                     fun_name="jit(mine)")
    log.on_time_span(SPAN("backend_compile"), 52.0, 53.0,
                     fun_name="jit(mine)")
    assert [e["fun"] for e in log.entries() if e["fun"] != "multiply"] == [
        "jit(a)", "mine", "jit(mine)", "jit(mine)"]
    assert log.dropped == 0 and len(log._runs) == 1
    go.set()
    other.join(10)
    assert not other.is_alive()
    # the thread went with its trace unfinished: its eight entries stand,
    # the twelve it gave up are counted, and the oldest that stood go
    assert [e["fun"] for e in log.entries()] == ["multiply"] * 8
    assert log.dropped == 12 + 4 and log._runs == {}
    assert log.programs_built == 2


def test_a_cache_answer_goes_to_its_own_threads_next_compile():
    log = profiling.CompileLog()
    log.on_event("/jax/compilation_cache/cache_hits")
    other = threading.Thread(target=lambda: (
        log.on_event("/jax/compilation_cache/cache_misses"),
        log.on_time_span(SPAN("backend_compile"), 1.0, 2.0,
                         fun_name="jit(theirs)")))
    other.start()
    other.join(10)
    assert not other.is_alive()
    log.on_event("/jax/compilation_cache/compile_requests_use_cache")
    log.on_time_span(SPAN("jaxpr_trace"), 2.0, 3.0, fun_name="mine")
    log.on_time_span(SPAN("backend_compile"), 3.0, 4.0,
                     fun_name="jit(mine)")
    log.on_time_span(SPAN("backend_compile"), 5.0, 6.0,
                     fun_name="jit(too_quick_to_keep)")
    assert [(e["fun"], e["cache"]) for e in log.entries()] == [
        ("jit(theirs)", "miss"), ("mine", None), ("jit(mine)", "hit"),
        ("jit(too_quick_to_keep)", None)]


def test_the_device_gauge_reads_the_one_log():
    """telemetry's own listener is gone: the gauge is the log's count,
    from the process's first logged program on."""
    from ray_tpu._private import telemetry
    from ray_tpu.util import metrics as M

    assert not hasattr(telemetry, "_on_jax_duration")
    assert not hasattr(telemetry, "_programs_built")
    profiling.compile_log()
    before = profiling.COMPILES.programs_built
    jax.block_until_ready(jax.jit(lambda x: x * 5 + before)(1.0))
    assert profiling.COMPILES.programs_built == before + 1
    if telemetry.enabled:
        telemetry.flush_device_gauges()
        gauge = M._REGISTRY["device_programs_built"]
        assert gauge._samples()[0][2] == before + 1


def test_the_unread_helpers_are_gone():
    for name in ("Timer", "profile", "device_memory_stats"):
        assert not hasattr(profiling, name)


# ---------------------------------------------------------------------------
# The benchmark's nine readers, on the timeline recorded on the chip
# ---------------------------------------------------------------------------
RECORDED = os.path.join(ROOT, "chipbench", "tests", "recorded",
                        "run_timeline")
SPLIT = ("cluster_start_s", "worker_start_s", "backend_start_s",
         "trace_lower_s", "compile_s", "cache_read_s", "setup_exec_s",
         "programs_built", "cache_hit_share")


@pytest.fixture
def recorded(monkeypatch, tmp_path):
    """(record, expected.json) with the recorded file where a run of its
    cell would have left it, under a stand-in for the checkout."""
    from chipbench import harness

    with open(os.path.join(RECORDED, "expected.json")) as f:
        want = json.load(f)
    cell = want["cell"]
    where = tmp_path / "chipbench_out" / cell / "train" / cell
    where.mkdir(parents=True)
    with open(os.path.join(RECORDED, "run_timeline.json")) as f:
        (where / "run_timeline.json").write_text(f.read())
    monkeypatch.setattr(harness, "REPO", str(tmp_path))
    return {"cell": {"name": cell},
            "window_start_unix": want["window_start_unix"]}, want


@pytest.mark.parametrize("metric", SPLIT)
def test_reader_on_the_recorded_timeline(recorded, metric):
    from chipbench import harness

    record, want = recorded
    got = harness.reader(metric).read(record)
    assert got == pytest.approx(want["metrics"][metric], abs=1e-6)
    # a run that is not this file's: its window opened after fit() ended
    later = dict(record, window_start_unix=record["window_start_unix"] + 1e6)
    assert harness.reader(metric).read(later) is None
    assert harness.reader(metric).read({"counters": {}, "trace": {}}) is None


def test_step_build_s_is_the_programs_own_step_in_the_log(
        recorded, monkeypatch, tmp_path):
    """`step_build_s`: the compile log's seconds for `train_step` and
    `jit(train_step)`, trace, lower, compile or cache read, each second
    once, cut to rank 0's loop up to the window. On the recorded timeline
    (a warm cache: 5.68 s of reading the step back) and on a hand-made
    one."""
    from chipbench import harness

    record, want = recorded
    read = harness.reader("step_build_s").read
    split = sum(want["metrics"][m] for m in (
        "trace_lower_s", "compile_s", "cache_read_s"))
    assert 5.68 < read(record) < split
    assert read(dict(record, window_start_unix=1e12)) is None

    def entry(fun, phase, start, end, cache=None):
        return {"fun": fun, "phase": phase, "start": start, "end": end,
                "cache": cache}
    spans = [{"name": "ray_tpu.train.fit", "start": 0.0, "end": 200.0},
             {"name": "ray_tpu.train.loop", "start": 10.0, "end": 190.0,
              "attributes": {"rank": 0}}]
    log = [entry("reference_loss", "trace", 11.0, 14.0),
           entry("jit(reference_loss)", "compile", 14.0, 30.0, "miss"),
           entry("train_step", "trace", 5.0, 12.0),      # 2 s in the loop
           entry("train_step", "trace", 40.0, 50.0),
           entry("multiply", "trace", 41.0, 42.0),       # inside it
           entry("jit(train_step)", "lower", 50.0, 55.0),
           entry("jit(train_step)", "compile", 55.0, 85.0, "hit"),
           entry("jit(train_step)", "compile", 95.0, 120.0, "miss")]
    where = tmp_path / "chipbench_out" / "made" / "train" / "made"
    where.mkdir(parents=True)
    (where / "run_timeline.json").write_text(json.dumps(
        {"spans": spans, "workers": {"0": {"compile_log": log}}}))
    monkeypatch.setattr(harness, "REPO", str(tmp_path))
    made = {"cell": {"name": "made"}, "window_start_unix": 100.0}
    # 2 + 10 + 5 + 30 + the 5 s of the last compile before the window
    assert read(made) == pytest.approx(52.0)
    assert harness.reader("compile_s").read(made) \
        + harness.reader("cache_read_s").read(made) \
        + harness.reader("trace_lower_s").read(made) == pytest.approx(70.0)
    m = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [x for x in m["per_layer"] if x["name"] == "step_build_s"] == [{
        "name": "step_build_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "train step", "moves": "setup_s",
        "workloads": [w["name"] for w in m["workloads"]]}]


def test_the_seven_times_add_up_to_the_runs_set_up(recorded):
    from chipbench import harness

    record, want = recorded
    seven = sum(harness.reader(m).read(record) for m in SPLIT[:7])
    assert want["metrics"]["backend_start_s"] > 1.0    # recorded on the chip
    with open(os.path.join(RECORDED, "run_timeline.json")) as f:
        spans = {s["name"]: s for s in json.load(f)["spans"]}
    init, fit = spans["ray_tpu.init"], spans["ray_tpu.train.fit"]
    assert seven == pytest.approx(
        record["window_start_unix"] - init["start"]
        - (fit["start"] - init["end"]), abs=1e-6)
    m = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in m["workloads"]]
    # nine entries in a row, wherever later PRs' metrics put them
    first = [x["name"] for x in m["per_layer"]].index(SPLIT[0])
    listed = {x["name"]: x for x in m["per_layer"][first:first + 9]}
    assert tuple(listed) == SPLIT
    assert all(x["workloads"] == cells and x["moves"] == "setup_s"
               and x["source"] == "host_clock" for x in listed.values())
    assert [x["better"] for x in listed.values()] == ["lower"] * 8 + ["higher"]
