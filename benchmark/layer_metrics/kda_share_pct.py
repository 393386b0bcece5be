"""Share of the device's busy time spent in the KDA mixers' own
operations: the gate, the short convolution with the norms of q and k,
the chunked form of a prompt's and a chunk's programs and the decode
step's pass over the state, as `benchmark/kda_ops.py` tells them (the
mixers' two projections are plain matrix products and are not counted),
over the busy time of the first device."""
from benchmark import kda_ops, trace_reduce


def read(observed):
    found = kda_ops.from_observed(observed)
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * (found["step"][0] + found["chunk"][0] + found["conv"]
                    + found["gate"]) / busy
