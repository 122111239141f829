"""Of the programs rank 0 built before the window, the share the
persistent compile cache answered, in percent: near 100 on a warm run, 0
on a first one. Small programs pull it down on any run: jax keeps only
what took XLA at least jax_persistent_cache_min_compile_time_secs to
build."""

from .cluster_start_s import compiles, run_timeline


def read(record):
    t = run_timeline(record)
    if t is None or not compiles(t):
        return None
    return 100.0 * len(compiles(t, True)) / len(compiles(t))
