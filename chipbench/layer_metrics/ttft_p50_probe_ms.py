"""Median over the probes (max_tokens=1 requests, whose reply is their
first token) answered in the window of reply time - due time."""


def read(record):
    return record["counters"].get("ttft_p50_ms")
