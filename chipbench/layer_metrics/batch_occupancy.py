"""Output tokens of requests completed in the window over the increase
of the replies' engine_steps in it: how full the decode batch ran."""


def read(record):
    c = record["counters"]
    if not c.get("engine_steps_in_window"):
        return None
    return c["out_tokens_in_window"] / c["engine_steps_in_window"]
