"""Seconds the TPU runtime took to start in the worker: the length of
the program's `ray_tpu.train.backend_start` span (import jax and the
first jax.local_devices(), before the loop). The program takes the span
only in a worker that was handed chips; without it (a CPU rehearsal) the
metric is left out and the start is inside setup_exec_s, at the loop's
first device call."""

from .cluster_start_s import length, run_timeline


def read(record):
    t = run_timeline(record)
    return t and length(t["backend"])
