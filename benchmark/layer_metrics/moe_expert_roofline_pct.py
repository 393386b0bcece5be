"""The expert products' share of their roofline in the traced window: the
least time the chip could take for the routed layers it ran over the
device time their operations took (`benchmark/moe_ops.py` tells which).

Least time, per layer call: `benchmark/flops_moe.py` on the call's rows
(read off the operations' shapes), its pairs (rows x experts per token:
the counters count every row a program computes) and the experts the
program's counters say were TOUCHED, never all of them by assumption: the
mean over the window's decode or prefill layer calls
(`engine_stats()["moe"]`), a traced call being a decode step's if its rows
are at most `max_batch_size`, and never more experts than the call has
pairs."""
from benchmark import flops_moe, moe_ops


def read(observed):
    found = moe_ops.from_observed(observed)
    if found is None:
        return None
    cfg = observed["config"]
    k, lanes = cfg["num_experts_per_tok"], cfg["engine"]["max_batch_size"]
    means = {}
    for kind in ("decode", "prefill"):
        c = flops_moe.moe_counters(observed, kind)
        if c and c["layer_calls"]:
            means[kind] = c["experts_touched"] / c["layer_calls"]
    took = least = 0.0
    for rows, (seconds, calls) in found["experts"].items():
        kind = "decode" if rows <= lanes else "prefill"
        if kind not in means:
            return None
        pairs = rows * k
        best, _ = flops_moe.expert_layer_least_seconds(
            pairs, min(means[kind], pairs), rows, cfg["hidden_size"],
            cfg["intermediate_size"], observed["device_kind"])
        took += seconds
        least += calls * best
    return 100.0 * least / took if took > 0 else None
