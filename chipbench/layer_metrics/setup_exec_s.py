"""Seconds of rank 0's loop, from its entry to the window's opening, in
which nothing was being traced, lowered, compiled or read from the
cache: programs running (initialisation, the reference check, the
warm-up steps), eager dispatch, host to device copies, the benchmark's
own Python; and the runtime's start where the program took no
`ray_tpu.train.backend_start` span. The `ray_tpu.train.loop` span's
start to the window, less the compile log's covered seconds
(cluster_start_s.py has the split)."""

from .cluster_start_s import run_timeline, split


def read(record):
    t = run_timeline(record)
    return t and split(t)["setup_exec_s"]
