"""Experts that received at least one pair, per routed layer of a decode
step: d`experts_touched` / d`layer_calls` of `engine_stats()["moe"]
["decode"]` over the window. A dispatch that reads only these reads this
share of a layer's expert weights."""
from benchmark.flops_moe import moe_counters


def read(observed):
    c = moe_counters(observed, "decode")
    if not c or not c["layer_calls"]:
        return None
    return c["experts_touched"] / c["layer_calls"]
