"""Median milliseconds of one batch inside Predictor.__call__:
the jitted predictor on the device batch, closed by block_until_ready."""


def read(record):
    return record["counters"].get("compute_ms_p50")
