"""The nine readers of a run's own timeline (layer_metrics/cluster_start_s
and the eight that import its loader): hand-worked values on a timeline
written here, and the numbers of the one recorded on the chip
(recorded/run_timeline/, PR 33) against a straight count over that file.
The file is the program's (`<experiment dir>/run_timeline.json`,
ray_tpu/train/v2/controller.py); a program that writes none, a file of
another run and a file without rank 0 give nothing and raise nothing."""

import copy
import json
import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "run_timeline")
READERS = ("cluster_start_s", "worker_start_s", "backend_start_s",
           "trace_lower_s", "compile_s", "cache_read_s", "setup_exec_s",
           "programs_built", "cache_hit_share")
CELL = "hand-cell"


def _span(name, start, end, parent=None, /, **attributes):
    return {"name": name, "trace_id": "t", "span_id": name + str(
        attributes.get("rank", "")), "parent_span_id": parent,
        "start": start, "end": end, "attributes": attributes or None,
        "error": None}


def _entry(phase, fun, start, end, cache=None):
    return {"fun": fun, "phase": phase, "start": start, "end": end,
            "cache": cache}


# By hand. init 2.0 s; fit() called at 102.5, rank 0's loop entered at
# 124.3 after 20.0 s of runtime start: worker_start_s = 124.3 - 102.5 - 20.0
# = 1.8. The window opens at 161.0. Of the loop's 36.7 s before it:
#   cache reads  g 1.0 + h 0.5                                   =  1.5
#   compiles     f 10.0 + k 0.2 (eager, inside h's trace)        = 10.2
#   trace, lower f 2.0 (`inner` lies inside it: once) + 1.0, g 1.0 + 0.5,
#                h 1.0 - 0.2 (k's compile counts there) + 0.5    =  5.8
#   the rest     36.7 - 17.5                                     = 19.2
# z ended before the loop began and w began inside the window: neither
# counts. Programs built: f, g, k, h = 4, the cache answered 2 = 50%.
# Seven times: 2.0 + 1.8 + 20.0 + 5.8 + 10.2 + 1.5 + 19.2 = 60.5
#            = 161.0 - 100.0 - (102.5 - 102.0).
WINDOW = 161.0
TIMELINE = {
    "trace_id": "t",
    "spans": [
        _span("ray_tpu.init", 100.0, 102.0),
        _span("ray_tpu.train.fit", 102.5, 200.0, name=CELL, num_workers=2),
        _span("ray_tpu.train.start_group", 102.6, 104.0,
              "ray_tpu.train.fit"),
        _span("ray_tpu.train.worker_setup", 103.5, 103.9,
              "ray_tpu.train.start_group", rank=0),
        _span("ray_tpu.train.worker_setup", 103.4, 103.8,
              "ray_tpu.train.start_group", rank=1),
        _span("ray_tpu.train.backend_start", 104.2, 124.2,
              "ray_tpu.train.fit", rank=0, platform="tpu"),
        _span("ray_tpu.train.backend_start", 104.1, 127.1,
              "ray_tpu.train.fit", rank=1, platform="tpu"),
        _span("ray_tpu.train.loop", 124.3, 199.0, "ray_tpu.train.fit",
              rank=0),
        _span("ray_tpu.train.loop", 127.2, 199.5, "ray_tpu.train.fit",
              rank=1),
        _span("ray_tpu.train.first_report", 160.0, 160.0,
              "ray_tpu.train.loop0"),
    ],
    "workers": {
        "0": {"pid": 11, "dropped": 0, "compile_log": [
            _entry("compile", "jit(z)", 120.0, 121.0, "miss"),
            _entry("trace", "inner", 125.5, 126.0),
            _entry("trace", "f", 125.0, 127.0),
            _entry("lower", "jit(f)", 127.0, 128.0),
            _entry("compile", "jit(f)", 128.0, 138.0, "miss"),
            _entry("trace", "g", 140.0, 141.0),
            _entry("lower", "jit(g)", 141.0, 141.5),
            _entry("compile", "jit(g)", 141.5, 142.5, "hit"),
            _entry("compile", "jit(k)", 143.2, 143.4),
            _entry("trace", "h", 143.0, 144.0),
            _entry("lower", "jit(h)", 144.0, 144.5),
            _entry("compile", "jit(h)", 144.5, 145.0, "hit"),
            _entry("compile", "jit(w)", 170.0, 171.0, "miss"),
        ]},
        "1": {"pid": 12, "dropped": 0, "compile_log": [
            _entry("compile", "jit(f)", 128.0, 158.0, "miss")]},
    },
}
BY_HAND = {"cluster_start_s": 2.0, "worker_start_s": 1.8,
           "backend_start_s": 20.0, "trace_lower_s": 5.8,
           "compile_s": 10.2, "cache_read_s": 1.5, "setup_exec_s": 19.2,
           "programs_built": 4, "cache_hit_share": 50.0}


@pytest.fixture
def checkout(monkeypatch, tmp_path):
    """A stand-in for the checkout: write(timeline, cell) puts a file
    where a run of `cell` leaves it and returns that run's record."""
    monkeypatch.setattr(harness, "REPO", str(tmp_path))

    def write(timeline, cell=CELL, window=WINDOW):
        where = tmp_path / "chipbench_out" / cell / "train" / cell
        where.mkdir(parents=True, exist_ok=True)
        text = timeline if isinstance(timeline, str) \
            else json.dumps(timeline)
        (where / "run_timeline.json").write_text(text)
        return {"cell": {"name": cell}, "window_start_unix": window,
                "counters": {}, "trace": {}}
    return write


def _read(record):
    return {name: harness.reader(name).read(record) for name in READERS}


def test_hand_worked_values(checkout):
    got = _read(checkout(TIMELINE))
    assert got == {k: pytest.approx(v, abs=1e-9) for k, v in BY_HAND.items()}
    assert sum(got[name] for name in READERS[:7]) == pytest.approx(
        WINDOW - 100.0 - (102.5 - 102.0), abs=1e-9)


def test_nested_trace_intervals_count_once(checkout):
    from chipbench.layer_metrics.cluster_start_s import union_s

    assert union_s([]) == 0.0
    assert union_s([{"start": 1.0, "end": 3.0}, {"start": 1.5, "end": 2.0},
                    {"start": 2.5, "end": 4.0}, {"start": 6.0, "end": 7.0}
                    ]) == pytest.approx(4.0)
    without = copy.deepcopy(TIMELINE)
    del without["workers"]["0"]["compile_log"][1]     # `inner`
    assert _read(checkout(without))["trace_lower_s"] == pytest.approx(5.8)


def test_without_a_backend_start_span(checkout):
    """A CPU worker takes none: the metric is left out, worker_start_s
    subtracts nothing and the runtime's start is the loop's."""
    cpu = copy.deepcopy(TIMELINE)
    cpu["spans"] = [s for s in cpu["spans"]
                    if s["name"] != "ray_tpu.train.backend_start"]
    got = _read(checkout(cpu))
    assert got["backend_start_s"] is None
    assert got["worker_start_s"] == pytest.approx(21.8)
    assert {k: v for k, v in got.items() if k not in (
        "backend_start_s", "worker_start_s")} == {
            k: pytest.approx(v) for k, v in BY_HAND.items() if k not in (
                "backend_start_s", "worker_start_s")}


def _no_rank_0(t):
    del t["workers"]["0"]


def _no_loop_of_rank_0(t):
    t["spans"] = [s for s in t["spans"] if s["span_id"]
                  != "ray_tpu.train.loop0"]


def _fit_still_open(t):
    t["spans"][1]["end"] = None


NOTHING_TO_READ = {
    "a file of an earlier run": dict(window=300.0),
    "a file of a later run": dict(window=101.0),
    "no rank 0 among the workers": dict(edit=_no_rank_0),
    "no loop span of rank 0": dict(edit=_no_loop_of_rank_0),
    "a file written while the run ran": dict(edit=_fit_still_open),
    "not JSON": dict(text="{"),
}


@pytest.mark.parametrize("case", NOTHING_TO_READ)
def test_gives_nothing_and_raises_nothing(checkout, case):
    how = NOTHING_TO_READ[case]
    timeline = copy.deepcopy(TIMELINE)
    how.get("edit", lambda t: None)(timeline)
    record = checkout(how.get("text", timeline),
                      window=how.get("window", WINDOW))
    assert _read(record) == dict.fromkeys(READERS)


def test_a_program_that_writes_no_timeline(checkout):
    record = checkout(TIMELINE, cell="another-cell")
    record["cell"]["name"] = "a-cell-that-never-ran"
    assert _read(record) == dict.fromkeys(READERS)
    # the manifest test's record: no cell, no window
    empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
    assert _read(empty) == dict.fromkeys(READERS)


def test_no_programs_before_the_window(checkout):
    idle = copy.deepcopy(TIMELINE)
    idle["workers"]["0"]["compile_log"] = []
    got = _read(checkout(idle))
    assert got["programs_built"] == 0 and got["cache_hit_share"] is None
    assert got["setup_exec_s"] == pytest.approx(WINDOW - 124.3)


# ---------------------------------------------------------------------------
# The timeline recorded on the chip
# ---------------------------------------------------------------------------
def _straight_count(doc, window):
    """The nine numbers by the slowest honest route: a millisecond grid
    over rank 0's loop up to the window, each millisecond given to the
    first of cache read, compile, trace or lower that covers it."""
    spans = {(s["name"], (s.get("attributes") or {}).get("rank")): s
             for s in doc["spans"]}
    init, fit = spans["ray_tpu.init", None], spans["ray_tpu.train.fit", None]
    loop = spans["ray_tpu.train.loop", 0]
    backend = spans.get(("ray_tpu.train.backend_start", 0))
    log = [e for e in doc["workers"]["0"]["compile_log"]
           if e["end"] > loop["start"] and e["start"] < window]
    step, kinds = 1e-3, {"read": 0, "compile": 0, "trace_lower": 0}
    t = loop["start"] + step / 2
    while t < window:
        here = [e for e in log if e["start"] <= t < e["end"]]
        if any(e["phase"] == "compile" and e["cache"] == "hit"
               for e in here):
            kinds["read"] += 1
        elif any(e["phase"] == "compile" for e in here):
            kinds["compile"] += 1
        elif here:
            kinds["trace_lower"] += 1
        t += step
    built = [e for e in log if e["phase"] == "compile"]
    took = (backend["end"] - backend["start"]) if backend else 0.0
    return {
        "cluster_start_s": init["end"] - init["start"],
        "worker_start_s": loop["start"] - fit["start"] - took,
        "backend_start_s": took if backend else None,
        "cache_read_s": kinds["read"] * step,
        "compile_s": kinds["compile"] * step,
        "trace_lower_s": kinds["trace_lower"] * step,
        "setup_exec_s": window - loop["start"] - sum(kinds.values()) * step,
        "programs_built": len(built),
        "cache_hit_share": 100.0 * sum(
            e["cache"] == "hit" for e in built) / len(built)}


def test_readers_on_the_recorded_timeline(checkout):
    want = harness.load_json(os.path.join(RECORDED, "expected.json"))
    with open(os.path.join(RECORDED, "run_timeline.json")) as f:
        text = f.read()
    record = checkout(text, cell=want["cell"],
                      window=want["window_start_unix"])
    got = _read(record)
    assert got == {k: pytest.approx(v, abs=1e-6)
                   for k, v in want["metrics"].items()}
    # a grid of milliseconds is off by at most one a boundary
    count = _straight_count(json.loads(text), want["window_start_unix"])
    edges = 2 * len(json.loads(text)["workers"]["0"]["compile_log"])
    for name in READERS:
        assert got[name] == pytest.approx(count[name], abs=1e-3 * edges), name
    assert got["programs_built"] == count["programs_built"]
    # ... and the file is one the program's own tools read
    from ray_tpu.util import tracing
    tree = tracing.build_trace(json.loads(text)["spans"])
    assert [r["name"] for r in tree["roots"]] == ["ray_tpu.init",
                                                  "ray_tpu.train.fit"]
