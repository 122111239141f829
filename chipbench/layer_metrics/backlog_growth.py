"""Requests in flight when the window closes minus those in flight when
it opens. Near 0 below the knee; a rise means the offered rate is not
sustained and the latencies of the window flatter the system."""


def read(record):
    c = record["counters"]
    if "inflight_at_close" not in c:
        return None
    return float(c["inflight_at_close"] - c["inflight_at_open"])
