"""Sequences preempted on cache exhaustion inside the window."""
from benchmark.readers import counter_delta


def read(observed):
    return counter_delta(observed, "preemptions")
