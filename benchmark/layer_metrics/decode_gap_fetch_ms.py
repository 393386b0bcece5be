"""The part of `decode_gap_ms` under `llm.fetch`: the copy of tokens and
logits to the host after the program ended (ROADMAP S3)."""
from benchmark.span_gaps import mean_gap_ms


def read(observed):
    return mean_gap_ms(observed, "decode", "fetch")
