"""Mean idle time of the first device inside one `llm.step.prefill` step's
interval (previous step's end to its own end), `llm.idle` left out."""
from benchmark.span_gaps import mean_gap_ms


def read(observed):
    return mean_gap_ms(observed, "prefill")
