"""The part of `prefill_gap_ms` under `llm.fetch`: the copy of the token and
the last position's logits to the host after the program ended."""
from benchmark.span_gaps import mean_gap_ms


def read(observed):
    return mean_gap_ms(observed, "prefill", "fetch")
