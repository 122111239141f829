"""Output tokens of requests completed in the window over the window.
Below the knee it equals the offered rate; a fall means a backlog."""


def read(record):
    c = record["counters"]
    if "out_tokens_in_window" not in c:
        return None
    return c["out_tokens_in_window"] / record["seconds"]
