"""Seconds `ray_tpu.init` took in the benchmark's parent: the cluster of
this run coming up (node, object store, log monitor, prestarted workers).
The length of the program's own `ray_tpu.init` span in the run's
`run_timeline.json` (ray_tpu/train/v2/controller.py writes it,
docs/OBSERVABILITY.md has its fields).

This file also holds what the other eight readers of that timeline share
(worker_start_s, backend_start_s, trace_lower_s, compile_s, cache_read_s,
setup_exec_s, programs_built, cache_hit_share): the loader and the split.
Set-up, from `ray_tpu.init`'s start to the window's opening, is seven
times that add up, less the gap between init's end and fit()'s start:

    cluster_start_s  init's length
    worker_start_s   fit()'s start -> rank 0's loop entered, less
    backend_start_s  the TPU runtime's start in the worker
    and, of rank 0's loop up to the window (what its compile log says,
    each second counted once: a cache read before a compile before
    tracing, where intervals of several threads overlap):
    cache_read_s     `compile` entries the persistent cache answered
    compile_s        `compile` entries it did not
    trace_lower_s    `trace` and `lower` entries
    setup_exec_s     the rest: programs running, eager dispatch, copies

A program that writes no timeline (the parent of PR 33), a file left by
an earlier run (its `ray_tpu.train.fit` span does not contain this run's
window start) or one without rank 0 leaves all nine out and raises
nothing."""

import json
import os

from .. import harness

FIT, INIT = "ray_tpu.train.fit", "ray_tpu.init"
LOOP, BACKEND = "ray_tpu.train.loop", "ray_tpu.train.backend_start"


def run_timeline(record):
    """{"window", "init", "fit", "loop", "backend", "log"} of this run, or
    None: the window's opening (unix seconds), the four spans (`init`
    and `backend` may be None) and rank 0's compile log cut to its loop
    up to the window."""
    cell = (record.get("cell") or {}).get("name")
    window = record.get("window_start_unix")
    if not cell or window is None:
        return None
    path = os.path.join(harness.REPO, "chipbench_out", cell, "train", cell,
                        "run_timeline.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    spans = [s for s in doc.get("spans", ()) if s.get("end") is not None]
    rank0 = [s for s in spans if (s.get("attributes") or {}).get("rank") == 0]

    def last(name, among):
        found = [s for s in among if s["name"] == name
                 and s["start"] <= window]
        return max(found, key=lambda s: s["start"]) if found else None

    fit, loop = last(FIT, spans), last(LOOP, rank0)
    worker = (doc.get("workers") or {}).get("0")
    if fit is None or fit["end"] < window or loop is None or worker is None:
        return None
    log = [dict(e, start=max(e["start"], loop["start"]),
                end=min(e["end"], window))
           for e in worker["compile_log"]
           if e["end"] > loop["start"] and e["start"] < window]
    return {"window": window, "init": last(INIT, spans), "fit": fit,
            "loop": loop, "backend": last(BACKEND, rank0), "log": log}


def length(span):
    return None if span is None else span["end"] - span["start"]


def union_s(entries):
    """Seconds covered by any of the entries' intervals: a jit traced
    inside another's trace is not counted twice."""
    total, covered_to = 0.0, float("-inf")
    for start, end in sorted((e["start"], e["end"]) for e in entries):
        if end > covered_to:
            total += end - max(start, covered_to)
            covered_to = end
    return total


def compiles(t, answered=None):
    """The log's `compile` entries: all, or those the persistent cache
    answered (True) or did not (False)."""
    return [e for e in t["log"] if e["phase"] == "compile"
            and (answered is None or (e["cache"] == "hit") == answered)]


def split(t):
    """{"cache_read_s", "compile_s", "trace_lower_s", "setup_exec_s"} of
    rank 0's loop up to the window."""
    read = union_s(compiles(t, True))
    built = union_s(compiles(t))
    logged = union_s(t["log"])
    return {"cache_read_s": read, "compile_s": built - read,
            "trace_lower_s": logged - built,
            "setup_exec_s": t["window"] - t["loop"]["start"] - logged}


def read(record):
    t = run_timeline(record)
    return t and length(t["init"])
