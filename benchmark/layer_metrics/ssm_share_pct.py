"""Share of the device's busy time spent in the Mamba-2 mixers' own
operations: the chunked scan, the decode step's state update, the
convolution and the gate with its grouped norm, as `benchmark/ssm_ops.py`
tells them (the mixers' two projections are plain matrix products and are
not counted), over the busy time of the first device."""
from benchmark import ssm_ops, trace_reduce


def read(observed):
    found = ssm_ops.from_observed(observed)
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * (found["step"][0] + found["scan"][0] + found["conv"]
                    + found["gate"]) / busy
