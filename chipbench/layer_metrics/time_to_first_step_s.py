"""Seconds from JaxTrainer.fit() in the parent to the end of the first
train step in the worker (process start, chip start-up, weights, compile
or cache read). Host clocks of two processes on one machine."""


def read(record):
    return record["counters"].get("time_to_first_step_s")
