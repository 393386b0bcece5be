"""Pairs of the most loaded expert over the mean expert's, inside the
window, prefill and decode steps together: the largest of the
`expert_pairs` deltas of `engine_stats()["moe"]` over their mean. 1.0 is a
router that spreads its pairs evenly."""
from benchmark.flops_moe import moe_counters


def read(observed):
    kinds = [c for c in (moe_counters(observed, k)
                         for k in ("prefill", "decode")) if c]
    if not kinds:
        return None
    per_expert = [sum(xs) for xs in zip(*(c["expert_pairs"] for c in kinds))]
    total = sum(per_expert)
    return max(per_expert) * len(per_expert) / total if total else None
