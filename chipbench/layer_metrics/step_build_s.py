"""Seconds of rank 0's set-up that went into building the program's own
train step: the entries of the program's compile log before the window
whose `fun` is `train_step` (its trace) or `jit(train_step)` (its
lowering, and its compile or the persistent cache's answer), over the
union of their intervals. What is left of trace_lower_s + compile_s +
cache_read_s is the benchmark's own programs (the reference loss, the
kernels' checks, initialisation), so a move of `setup_s` can be put to
one side or the other (cluster_start_s.py has the loader)."""

from .cluster_start_s import run_timeline, union_s

STEP = ("train_step", "jit(train_step)")


def read(record):
    t = run_timeline(record)
    return t and union_s([e for e in t["log"] if e.get("fun") in STEP])
