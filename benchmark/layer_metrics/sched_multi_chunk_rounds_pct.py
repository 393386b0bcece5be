"""Of the window's decode steps, the share that more than one continuation
chunk preceded: 100 x d`multi_chunk_rounds` / d`decode_steps`, the
scheduler's own counters (`Scheduler.depth()`, at the top level of
`engine_stats()`), after - before. Between two decode steps the scheduler
runs a chunk for each lane that was still prefilling at the first, so a
decoding lane's token waits for more than one chunk exactly in these
rounds: where a tail is judged (`itl_p95_ms`) the share says how often the
rule engages; with at most one lane prefilling it is 0 and the loop is one
chunk, one decode step. An admission's first chunk is no continuation
chunk. A program older than the counters, or a window with no decode step,
gives None."""
from benchmark.readers import counter_delta


def read(observed):
    steps = counter_delta(observed, "decode_steps")
    multi = counter_delta(observed, "multi_chunk_rounds")
    if not steps or multi is None:
        return None
    chunks = counter_delta(observed, "continuation_chunks")
    print(f"[sched] decode steps in the window: {steps:.0f}, {multi:.0f} "
          f"behind more than one of {chunks:.0f} continuation chunks",
          flush=True)
    return 100.0 * multi / steps
