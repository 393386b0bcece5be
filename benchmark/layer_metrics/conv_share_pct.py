"""Share of the device's busy time spent in the gated short-convolution
operators' operations (`benchmark/conv_ops.py`: `in_proj`, the gate, the
window's sum and its carried rows, `out_proj` with the residual sum XLA
fuses into it), over the busy time of the first device. 18 of this
model's 24 blocks have the operator; a configuration without its keys is
not this reader's."""
from benchmark import conv_ops, trace_reduce


def read(observed):
    found = conv_ops.from_observed(observed)
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * sum(s for s, _ in found.values()) / busy
