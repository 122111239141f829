"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, ...) per train step, mean over the chips' planes."""


def read(record):
    t = record.get("trace") or {}
    if "collective_s" not in t or record["counters"]["chips"] < 2:
        return None
    return 1e3 * t["collective_s"] / t["steps"]
