"""Device time of all-reduce operations on the first device, per traced
step (hidden or exposed: the trace alone cannot tell)."""
from benchmark.readers import trace_ops


def read(observed):
    hit = trace_ops(observed, r"^all-reduce")
    steps = observed.get("traced_steps")
    if hit is None or not steps:
        return None
    return 1e3 * hit[0] / steps
