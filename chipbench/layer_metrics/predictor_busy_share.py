"""Time inside the benchmark's Predictor.__call__ over the window: what
is left is the actor waiting for the feed."""


def read(record):
    c = record["counters"]
    if not c.get("predictor_calls"):
        return None
    return 100.0 * c["predictor_busy_s"] / c["window_s"]
