"""Operations and bytes a routed-expert layer REQUIRES, from its routing:
the chosen (token, expert) pairs and the experts that received any. What a
dispatch computes or reads beyond that (every expert for every row, an
expert no row chose) is the program's choice and does not count, so a share
of this roofline cannot pass 100%: a kernel that skips the experts no row
chose reads exactly the bytes counted here.
"""

from __future__ import annotations

from benchmark.flops import least_seconds

SWIGLU_MATRICES = 3  # gate, up, down: each d_model x d_ff


def expert_layer_flops(pairs: float, d_model: int, d_ff: int) -> float:
    """Each pair is one row through one expert: three products of
    2 * d_model * d_ff."""
    return pairs * SWIGLU_MATRICES * 2.0 * d_model * d_ff


def expert_layer_bytes(touched: float, rows: float, d_model: int, d_ff: int,
                       itemsize: int = 2) -> float:
    """The weights of the experts that received a pair, once, and the
    layer's rows in and out, once. Rows ordered by expert, the hidden
    activations and the weights of untouched experts need never cross
    HBM."""
    return touched * SWIGLU_MATRICES * d_model * d_ff * itemsize \
        + 2.0 * rows * d_model * itemsize


def expert_layer_least_seconds(pairs: float, touched: float, rows: float,
                               d_model: int, d_ff: int, device_kind: str
                               ) -> tuple[float, str]:
    return least_seconds(expert_layer_flops(pairs, d_model, d_ff),
                         expert_layer_bytes(touched, rows, d_model, d_ff),
                         device_kind)


def moe_counters(observed: dict, kind: str) -> dict | None:
    """after - before of the engine's routing account for one step kind
    (`engine_stats()["moe"][kind]`): pairs, experts_touched, layer_calls,
    and expert_pairs as a list. None where the program has no such
    counters (a dense model, or a commit before they existed)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    a = (observed["after"]["stats"].get("moe") or {}).get(kind)
    b = (observed["before"]["stats"].get("moe") or {}).get(kind)
    if not a:
        return None
    zero = {"pairs": 0, "experts_touched": 0, "layer_calls": 0,
            "expert_pairs": [0] * len(a["expert_pairs"])}
    b = b or zero
    return {"pairs": a["pairs"] - b["pairs"],
            "experts_touched": a["experts_touched"] - b["experts_touched"],
            "layer_calls": a["layer_calls"] - b["layer_calls"],
            "expert_pairs": [x - y for x, y in zip(a["expert_pairs"],
                                                   b["expert_pairs"])]}
