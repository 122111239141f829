"""Seconds from JaxTrainer.fit() being called to rank 0's loop being
entered, less the TPU runtime's start (backend_start_s, where the program
took that span): feasibility, the dataset split, the worker process and
its actor, `setup` and the backend's `on_start`, `run` submitted and
reached. From the program's spans `ray_tpu.train.fit` and
`ray_tpu.train.loop` (cluster_start_s.py has the loader)."""

from .cluster_start_s import length, run_timeline


def read(record):
    t = run_timeline(record)
    if t is None:
        return None
    return t["loop"]["start"] - t["fit"]["start"] \
        - (length(t["backend"]) or 0.0)
