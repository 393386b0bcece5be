"""Share of the device's busy time spent in the routed-expert layers: the
operations `benchmark/moe_ops.py` tells to be theirs (the experts' three
products with the weighted sum XLA fuses into the last, and the routing)
over the busy time of the first device."""
from benchmark import moe_ops, trace_reduce


def read(observed):
    found = moe_ops.from_observed(observed)
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * (sum(s for s, _ in found["experts"].values())
                    + found["routing"]) / busy
