"""Median milliseconds of one batch inside Predictor.__call__:
np.asarray of the label array."""


def read(record):
    return record["counters"].get("fetch_ms_p50")
