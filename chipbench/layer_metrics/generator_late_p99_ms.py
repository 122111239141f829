"""99th percentile of (send time - due time) of the load generator: a
starved generator must not be read as a fast server."""


def read(record):
    return record["counters"].get("generator_late_p99_ms")
