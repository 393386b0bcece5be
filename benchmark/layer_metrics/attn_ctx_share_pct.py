"""Share of the device's busy time spent reading the lanes' cached
context and folding it into the running softmax, both kinds of KV layer
(`attn_ops.ctx_ops`: the full kind's tile loops and the window kind's
one-tile operations; the projections and the program's own rows are not
counted), over the busy time of the first device."""
from benchmark import attn_ops, trace_reduce


def read(observed):
    events = observed.get("events")
    found = attn_ops.ctx_ops(events, observed["config"]) if events else None
    if not found or not found["loops"]:
        return None
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    took = found["window"] + sum(s for s, _ in found["loops"].values())
    return 100.0 * took / busy
