"""Seconds from serve.run() to the first HTTP 200 through the proxy."""


def read(record):
    return record["counters"].get("replica_ready_s")
