"""Programs rank 0 built before the window: the `compile` entries of the
program's compile log (every miss of jit's in-memory cache, whether XLA
compiled or the persistent cache answered). The benchmark's own count of
the same events is `counters.programs_built`; this one has each
program's name and seconds behind it in run_timeline.json."""

from .cluster_start_s import compiles, run_timeline


def read(record):
    t = run_timeline(record)
    return t and len(compiles(t))
