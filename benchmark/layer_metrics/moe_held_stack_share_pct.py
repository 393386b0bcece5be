"""Share of the device's busy time spent in the held experts' operations
where `moe_held_share_pct`'s reader would take the attention's output
projection for them (`moe_stack_ops.held_stack_ops`: the products stacked
over the held experts, and the first operation behind them that returns
the residual stream's shape: their down products and weighted sum; the
router and the shared expert are not counted), over the busy time of the
first device."""
from benchmark import moe_stack_ops, trace_reduce


def read(observed):
    cfg = observed["config"]
    if "moe_intermediate_size" not in cfg or "index_topk" not in cfg \
            or not observed.get("events"):
        return None
    events = observed["events"]
    found = moe_stack_ops.held_stack_ops(
        events, cfg["n_routed_experts"], cfg["moe_intermediate_size"],
        cfg["hidden_size"])
    if found is None:
        return None
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * sum(s for s, _ in found.values()) / busy
