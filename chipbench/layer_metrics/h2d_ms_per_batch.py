"""Median milliseconds of one batch inside Predictor.__call__:
jax.device_put of the float32 batch, closed by block_until_ready."""


def read(record):
    return record["counters"].get("h2d_ms_p50")
