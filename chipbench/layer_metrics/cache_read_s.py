"""Seconds of rank 0's set-up in which the persistent compile cache
answered: the key worked out, the entry read, deserialised and loaded
onto the device. The `compile` entries of the program's compile log
before the window whose `cache` is "hit", over the union of their
intervals (cluster_start_s.py has the split)."""

from .cluster_start_s import run_timeline, split


def read(record):
    t = run_timeline(record)
    return t and split(t)["cache_read_s"]
