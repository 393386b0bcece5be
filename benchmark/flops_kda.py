"""Operations and bytes a KDA mixer's recurrence REQUIRES (Kimi Delta
Attention: a state of d x d float32 a head, a decay a channel, the delta
rule), from its shapes: H heads of d, whatever implements it.

A decode step is one step of the recurrence a lane: the lane's state read
once and written once, its q, k, v, g (H d each) and beta (H) in, o out,
all float32 as the recurrence holds them. A prompt's or a chunk's rows go
through the chunked form at `block` rows a block, whose products are what
the algorithm needs at that block size: per block and head the two decayed
products ``K K^T`` and ``Q K^T`` (2 Q^2 d each), the unit lower-triangular
solve of d + d right-hand sides by substitution (2 Q^2 d), the rows ``U -
W_k S`` (2 Q d^2), the read-out (2 Q d^2 + 2 Q^2 d) and the state's update
(2 Q d^2): 8 Q^2 d + 6 Q d^2; the state read once and written once a
program and layer. What a program moves or multiplies beyond that (the slots no
lane of a step owns, a state read three times because a slice, a reduce
and an update did not fuse, float32 products made of several bf16 passes)
is its choice, so a share of this roofline cannot pass 100%. The products
are held to the chip's bf16 peak.
"""

from __future__ import annotations

from benchmark.flops import least_seconds

F32 = 4


def state_bytes(lanes: float, heads: int, head_dim: int) -> float:
    """A lane's state of one layer, read once and written once."""
    return lanes * 2.0 * heads * head_dim * head_dim * F32


def rows_bytes(rows: float, heads: int, head_dim: int) -> float:
    """q, k, v, g and beta in, o out, once a row."""
    return rows * F32 * heads * (5 * head_dim + 1)


def step_flops(lanes: float, heads: int, head_dim: int) -> float:
    """One step a lane: the decay (d^2), ``k^T S`` and ``q^T S`` (2 d^2
    each) and the rank-one update (2 d^2) a head."""
    return lanes * 7.0 * heads * head_dim * head_dim


def chunk_flops(rows: float, block: int, heads: int, head_dim: int) -> float:
    """The chunked form over `rows` rows in blocks of `block`."""
    q = min(block, rows)
    return rows / q * heads * (8.0 * q * q * head_dim
                               + 6.0 * q * head_dim * head_dim)


def step_least_seconds(lanes: float, heads: int, head_dim: int,
                       device_kind: str) -> tuple[float, str]:
    return least_seconds(
        step_flops(lanes, heads, head_dim),
        state_bytes(lanes, heads, head_dim)
        + rows_bytes(lanes, heads, head_dim), device_kind)


def chunk_least_seconds(rows: float, block: int, heads: int, head_dim: int,
                        device_kind: str) -> tuple[float, str]:
    return least_seconds(
        chunk_flops(rows, block, heads, head_dim),
        state_bytes(1, heads, head_dim) + rows_bytes(rows, heads, head_dim),
        device_kind)
