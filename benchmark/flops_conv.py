"""Operations and bytes a gated short-convolution operator REQUIRES, from
its shapes: hidden size D, a depthwise causal convolution over K rows.

    B | C | X = u Win (D -> 3 D);  g = B * X;  c_t = sum_j w_j g_{t-K+1+j};
    out = (C * c) Wout (D -> D)

Whatever implements it multiplies every row with the two matrices and
reads them once a call, reads the rows in and writes them out once, and
reads and writes once, for each lane, the K - 1 rows of g that the lane
carries from call to call (a prompt's or a chunk's call has one lane; a
decode step one a lane it owns). What a program moves beyond that (the
window of the slots no lane of a step owns, B | C | X written to memory
between the product and the gate) is its choice, so a share of this
roofline cannot pass 100% of a time that holds all of the work. There
is ONE requirement, with the weights: where a program moves their read
out of the events a reader times (XLA's asynchronous fetch under the layer
before), the reader keeps to the calls whose least time the products set
(`layer_metrics/conv_roofline_pct.py`), and leaves the requirement alone.
"""

from __future__ import annotations

from benchmark.flops import least_seconds


def conv_operator_flops(rows: float, d: int, k: int) -> float:
    """The two products (2 x rows x D x 3 D and 2 x rows x D x D), the
    gate's two elementwise products and the K multiply-adds of the
    convolution, a row and channel."""
    return rows * (8.0 * d * d + d * (2.0 + 2.0 * k))


def conv_operator_bytes(rows: float, lanes: float, d: int, k: int,
                        itemsize: int = 2) -> float:
    """`in_proj`, `out_proj` and the K taps once; the rows in and out
    once; K - 1 carried rows a lane read once and written once."""
    return (4.0 * d * d + k * d) * itemsize + 2.0 * rows * d * itemsize \
        + 2.0 * lanes * (k - 1) * d * itemsize


def conv_operator_least_seconds(rows: float, lanes: float, d: int, k: int,
                                device_kind: str) -> tuple[float, str]:
    """(the least time of one call, which peak sets it: "compute" from
    256 rows on at hidden 2048 on a v5e, "memory" below)."""
    return least_seconds(conv_operator_flops(rows, d, k),
                         conv_operator_bytes(rows, lanes, d, k), device_kind)
