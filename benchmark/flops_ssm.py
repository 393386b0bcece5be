"""Operations and bytes a Mamba-2 mixer's recurrence REQUIRES, from its
shapes: H heads of P, a state of N a head, G groups sharing B and C.

A prompt's or a chunk's rows go through the chunked (SSD) form at Q rows a
chunk, whose products are what the algorithm needs at that chunk size; a
decode step is one step of the recurrence a lane. Either way the state,
float32, is read once and written once a lane and layer, and that is all
the memory traffic counted beside the rows' own inputs and outputs: what a
program moves beyond it (the slots no lane of a step owns, a state read
twice because the read-out did not fuse with the update) is its choice, so
a share of this roofline cannot pass 100%.
"""

from __future__ import annotations

from benchmark.flops import least_seconds

STATE_ITEMSIZE = 4  # float32


def scan_flops(rows: int, chunk: int, heads: int, head_dim: int, state: int,
               groups: int) -> float:
    """The chunked form over `rows` rows: per chunk of Q rows C B^T (G
    products of Q x Q x N), the masked product with x (H of Q x Q x P), the
    read-out of the carried state and its update (H of Q x P x N each)."""
    q = min(chunk, rows)
    chunks = rows / q
    return chunks * (2.0 * q * q * (state * groups + head_dim * heads)
                     + 4.0 * q * head_dim * state * heads)


def step_flops(lanes: float, heads: int, head_dim: int, state: int) -> float:
    """One step of the recurrence a lane: the state's multiply-add and the
    read-out's, 2 H P N each."""
    return lanes * 4.0 * heads * head_dim * state


def state_bytes(lanes: float, heads: int, head_dim: int, state: int) -> float:
    """A lane's state of one layer, read once and written once."""
    return lanes * 2.0 * heads * head_dim * state * STATE_ITEMSIZE


def rows_bytes(rows: float, heads: int, head_dim: int, state: int,
               groups: int, itemsize: int = 2) -> float:
    """x, B, C and dt in, y out, once a row."""
    return rows * itemsize * (2 * heads * head_dim + 2 * groups * state
                              + heads)


def scan_least_seconds(rows: int, chunk: int, heads: int, head_dim: int,
                       state: int, groups: int, device_kind: str
                       ) -> tuple[float, str]:
    return least_seconds(
        scan_flops(rows, chunk, heads, head_dim, state, groups),
        state_bytes(1, heads, head_dim, state)
        + rows_bytes(rows, heads, head_dim, state, groups), device_kind)


def step_least_seconds(lanes: float, heads: int, head_dim: int, state: int,
                       groups: int, device_kind: str) -> tuple[float, str]:
    return least_seconds(
        step_flops(lanes, heads, head_dim, state),
        state_bytes(lanes, heads, head_dim, state)
        + rows_bytes(lanes, heads, head_dim, state, groups), device_kind)
