"""Bytes and operations that a hyper-connected half-layer's COEFFICIENT
MAPS require (ray_tpu/models/xing4.py `mhc_coefficients`): the token's
state X, n streams of C lanes in the compute dtype, read once for the
product of its normed vector with `phi` (n C x (n^2 + 2n), float32, read
once a program and half-layer), the logits written and read once and the
coefficients written once (n^2 + 2n float32 numbers a token each), and
that product's operations; the Sinkhorn iterations' few hundred
operations a token count for nothing beside them.

What is NOT here, and why: a half-layer also reads X twice more (the
state's mean square, a pass of its own in the program XLA compiles; the
two mixes, which read X and y and write X) but XLA:TPU fuses the mixes
into the products before and after them and returns the mean square as a
`[rows]` vector like any norm's, so no label of a trace tells those
operations from their neighbours' (`benchmark/mhc_ops.py`). The required
work below is that of the operations that CAN be told, so that the share
of the roofline compares like with like; the whole half-layer's traffic,
``rows x n C x 2 B x 4 + rows x C x 2 B`` beside `phi`, is what
`half_layer_bytes` gives for PERF.md's arithmetic.
"""

from __future__ import annotations

from benchmark.flops import least_seconds


def maps_bytes(rows: float, n: int, C: int, itemsize: int = 2) -> float:
    K = n * (n + 2)
    return rows * n * C * itemsize + n * C * K * 4.0 + 3.0 * rows * K * 4


def maps_flops(rows: float, n: int, C: int) -> float:
    return 2.0 * rows * n * C * n * (n + 2)


def half_layer_bytes(rows: float, n: int, C: int, itemsize: int = 2) -> float:
    """X read for the mean square, the product and the two mixes and
    written once, y read once, `phi` once."""
    return rows * (4 * n * C + C) * itemsize + n * C * n * (n + 2) * 4.0


def program_least_seconds(cfg: dict, rows: float, device_kind: str
                          ) -> tuple[float, str]:
    """(the least seconds the chip could take for the maps of ONE program
    of `rows` rows, both half-layers of every layer, the bounding peak)."""
    halves = 2 * cfg["num_hidden_layers"]
    n, C = cfg["hc_mult"], cfg["hidden_size"]
    return least_seconds(halves * maps_flops(rows, n, C),
                         halves * maps_bytes(rows, n, C), device_kind)
