"""Seconds of rank 0's set-up in which XLA built an executable because
the persistent cache had none: the `compile` entries of the program's
compile log before the window whose `cache` is "miss" (compiled and
written) or null (the cache not asked, or the program built too quickly
to be kept), over the union of their intervals, less what a cache read
running meanwhile already counts (cluster_start_s.py has the split)."""

from .cluster_start_s import run_timeline, split


def read(record):
    t = run_timeline(record)
    return t and split(t)["compile_s"]
