"""Share of the device's busy time spent in `copy` operations whose result
has the KV pool's shape (the relayout around the 8-row scatter)."""
from benchmark import trace_reduce
from benchmark.readers import trace_ops


def read(observed):
    cfg = observed["config"]
    e = cfg["engine"]
    shape = ",".join(str(x) for x in (
        cfg["n_layer"], e["num_blocks"], e["block_size"], cfg["n_head"],
        cfg["n_embd"] // cfg["n_head"]))
    hit = trace_ops(observed, rf"^\S+ copy [a-z0-9]+\[{shape}\]$")
    if hit is None:
        return None
    events = observed["events"]
    planes = trace_reduce.device_planes(events)
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(events,
                                                             planes[0]))
    return 100.0 * hit[0] * 1e9 / busy if busy > 0 else None
