"""The held GATED experts' share of their roofline in the traced window,
as `moe_held_glu_roofline_pct`, with the operations told by
`moe_stack_ops.held_stack_ops` (that reader's would take this model's
attention output projection for an expert layer): the least time the chip
could take for the routed layers it ran (`benchmark/flops_moe_held_glu.py`:
three matrices an expert, the pairs that landed on experts held here, the
bytes of the held experts touched) over the device time the experts'
operations took.

Per layer call: its pairs are rows x experts per token x the share of the
window's pairs that landed on held experts, and its experts touched the
mean over the window's decode or prefill layer calls, never more than the
call has pairs; a traced call is a decode step's if its rows are at most
`max_batch_size`. At 8 of 256 experts held a row has a quarter of a pair
here, so a decode step requires little of the 0.6 GB a layer it reads:
the share says what every held expert for every row costs at one of
thirty-two chips' traffic."""
from benchmark import flops_moe_held, flops_moe_held_glu, moe_stack_ops


def read(observed):
    cfg = observed["config"]
    if "moe_intermediate_size" not in cfg or "index_topk" not in cfg \
            or cfg.get("hidden_act") != "silu" or not observed.get("events"):
        return None
    found = moe_stack_ops.held_stack_ops(
        observed["events"], cfg["n_routed_experts"],
        cfg["moe_intermediate_size"], cfg["hidden_size"])
    if found is None:
        return None
    k, lanes = cfg["num_experts_per_tok"], cfg["engine"]["max_batch_size"]
    per_call = {}
    for kind in ("decode", "prefill"):
        c = flops_moe_held.held_counters(observed, kind)
        if c and c["layer_calls"] and c["pairs"]:
            per_call[kind] = (c["held_pairs"] / c["pairs"],
                              c["held_experts_touched"] / c["layer_calls"])
    took = least = 0.0
    for rows, (seconds, calls) in found.items():
        kind = "decode" if rows <= lanes else "prefill"
        if kind not in per_call:
            return None
        share, touched = per_call[kind]
        pairs = rows * k * share
        best, _ = flops_moe_held_glu.held_layer_least_seconds(
            pairs, min(touched, pairs), rows, cfg["hidden_size"],
            cfg["moe_intermediate_size"], observed["device_kind"])
        took += seconds
        least += calls * best
    return 100.0 * least / took if took > 0 else None
