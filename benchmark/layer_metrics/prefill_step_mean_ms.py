"""Mean engine step of kind prefill (one chunk) inside the window."""
from benchmark.readers import histogram_mean


def read(observed):
    return histogram_mean(observed, "serve_llm_step_ms", kind="prefill")
