"""Mean engine step of kind decode inside the window (host clock around
the blocking dispatch: serve_llm_step_ms{kind="decode"})."""
from benchmark.readers import histogram_mean


def read(observed):
    return histogram_mean(observed, "serve_llm_step_ms", kind="decode")
