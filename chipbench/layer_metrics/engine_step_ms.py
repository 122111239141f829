"""Window over the increase of engine_steps: the time of one pass of the
engine loop (admissions and their prefills, one decode step, the copy
of the logits to the host, sampling)."""


def read(record):
    c = record["counters"]
    if not c.get("engine_steps_in_window"):
        return None
    return 1e3 * c["engine_steps_span_s"] / c["engine_steps_in_window"]
