"""90th percentile over the probes answered in the window of reply time
- due time. With a dozen probes in a window it is close to their
largest, which is why it is not an end-to-end metric yet (PERF.md)."""


def read(record):
    return record["counters"].get("ttft_p90_ms")
