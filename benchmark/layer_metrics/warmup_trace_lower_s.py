"""The Python part of warm-up: jax tracing and lowering every bucketed
program (`warmup_trace` + `warmup_lower`), paid on every start."""
from benchmark.startup import startup


def read(observed):
    up = startup(observed)
    return None if up is None else up["warmup_trace"] + up["warmup_lower"]
