"""Operations and bytes the HELD share of a routed layer of GATED experts
requires: as `benchmark/flops_moe_held.py`, with three matrices an expert
(gate, up, down: SwiGLU) where that file counts two. The chosen (token,
expert) pairs that landed on experts this chip holds, and those of its
experts that received any; what a dispatch computes or reads beyond the
pairs (every held expert for every row) is the program's choice and does
not count. Which operations are the held experts' is
`flops_moe_held.held_expert_ops`'s to say: it tells them by the stacked
products' results (`bf16[held, rows, d_ff]` and the like) and the weighted
sum's (`bf16[rows, d_model]`, where it takes longer than reading a quarter
of TWO matrices an expert would: a lower floor than three would give, and
no other operation of this model that returns one result of that shape
comes near it)."""

from __future__ import annotations

from benchmark.flops import least_seconds

MATRICES = 3  # gate, up, down: each d_model x d_ff


def held_layer_flops(pairs: float, d_model: int, d_ff: int) -> float:
    return pairs * MATRICES * 2.0 * d_model * d_ff


def held_layer_bytes(touched: float, rows: float, d_model: int, d_ff: int,
                     itemsize: int = 2) -> float:
    """The weights of the held experts that received a pair, once, and
    the layer's rows in and out, once."""
    return touched * MATRICES * d_model * d_ff * itemsize \
        + 2.0 * rows * d_model * itemsize


def held_layer_least_seconds(pairs: float, touched: float, rows: float,
                             d_model: int, d_ff: int, device_kind: str
                             ) -> tuple[float, str]:
    return least_seconds(held_layer_flops(pairs, d_model, d_ff),
                         held_layer_bytes(touched, rows, d_model, d_ff),
                         device_kind)
