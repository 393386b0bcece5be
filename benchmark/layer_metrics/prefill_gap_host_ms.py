"""The part of `prefill_gap_ms` under neither `llm.fetch` nor
`llm.dispatch`: Python the device waits out. The remainder of the gap is
`llm.dispatch`."""
from benchmark.span_gaps import mean_gap_ms


def read(observed):
    return mean_gap_ms(observed, "prefill", "host")
