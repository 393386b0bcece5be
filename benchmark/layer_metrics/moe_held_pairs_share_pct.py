"""Share of the router's (token, expert) pairs that landed on the experts
this chip holds, prefill and decode steps together, inside the window:
d`held_pairs` / d`pairs` of `engine_stats()["moe"]`. 100 x held / routed
experts (25% here) under even routing; the rest is what the absent chips
of the deployment would compute."""
from benchmark.flops_moe_held import held_counters


def read(observed):
    kinds = [c for c in (held_counters(observed, k)
                         for k in ("prefill", "decode")) if c]
    pairs = sum(c["pairs"] for c in kinds)
    if not pairs:
        return None
    return 100.0 * sum(c["held_pairs"] for c in kinds) / pairs
