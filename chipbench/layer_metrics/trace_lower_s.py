"""Seconds of rank 0's set-up spent tracing functions into jaxprs and
lowering them to StableHLO: Python's work, which no cache saves. The
`trace` and `lower` entries of the program's compile log
(ray_tpu/util/profiling.py compile_log) before the window, over the
union of their intervals, less what a compile running meanwhile already
counts (cluster_start_s.py has the split)."""

from .cluster_start_s import run_timeline, split


def read(record):
    t = run_timeline(record)
    return t and split(t)["trace_lower_s"]
