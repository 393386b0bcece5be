"""Share of the device's busy time spent in the held experts' operations
(`flops_moe_held.held_expert_ops`: the stacked products and the weighted
sum they are fused into; the router and the shared expert are not
counted), over the busy time of the first device."""
from benchmark import flops_moe_held, trace_reduce


def read(observed):
    cfg = observed["config"]
    if "moe_intermediate_size" not in cfg or not observed.get("events"):
        return None
    found = flops_moe_held.held_expert_ops(
        observed["events"], cfg["n_routed_experts"],
        cfg["moe_intermediate_size"], cfg["hidden_size"],
        observed["device_kind"])
    if found is None:
        return None
    events = observed["events"]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9
    if not busy > 0:
        return None
    return 100.0 * sum(s for s, _ in found.values()) / busy
