"""Share of the device's busy time spent in the one-step recurrence of a
one-group Mamba-2 mixer: the decode steps' in-place update of every
slot's state, the read-out y and the lanes' inputs and outputs put in slot
order, as `benchmark/ssm_g1_ops.py` tells them (the convolution, the gate
and the two projections are not counted), over the busy time of the first
device."""
from benchmark import ssm_g1_ops, trace_reduce


def read(observed):
    found = ssm_g1_ops.from_observed(observed)
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * found["step"][0] / busy
