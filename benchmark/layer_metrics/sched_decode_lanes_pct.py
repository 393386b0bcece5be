"""Of the engine's lanes, the share that a decode step of the window moved
one token on: 100 x d`decode_lanes` / (d`decode_steps` x `max_batch_size`),
the scheduler's own counters (`Scheduler.depth()`, which `engine_stats()`
spreads at its top level), after - before. `decode_steps` counts the decode
steps `schedule()` planned and `decode_lanes` the ready lanes summed over
them, whatever the family (`decode_lanes_per_step` reads a counter that only
a family with recurrent state keeps). `batch_occupancy` says how many lanes
hold a sequence; this says how many of them share a decode step's read of
the whole tree. The scheduler runs a chunk for each lane still prefilling
and then one decode step, so a saturated cell reads near 100 x R / (R + P)
with R / P = output tokens / continuation chunks a request. A program older
than the counters has no such key, and a window with no decode step nothing
to divide by: both give None."""
from benchmark.readers import counter_delta


def read(observed):
    steps = counter_delta(observed, "decode_steps")
    lanes = counter_delta(observed, "decode_lanes")
    if not steps or lanes is None:
        return None
    rows = observed["after"]["stats"]["max_batch_size"]
    print(f"[sched] decode steps in the window: {steps:.0f} of "
          f"{lanes / steps:.2f} lanes, of {rows}", flush=True)
    return 100.0 * lanes / (steps * rows)
