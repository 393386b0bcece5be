"""Bytes the decode steps inside the window fetched to the host (sampled
tokens and logits), per step: d`d2h_bytes["decode"]` / d`steps["decode"]`
of `engine_stats()`, in units of 1000 bytes."""
from benchmark.readers import counter_delta


def read(observed):
    fetched = counter_delta(observed, "d2h_bytes", "decode")
    steps = counter_delta(observed, "steps", "decode")
    if fetched is None or not steps:
        return None
    return fetched / steps / 1e3
