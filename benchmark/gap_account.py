"""Arithmetic over the engine's bucketed durations (`engine_stats()`:
`token_gaps`, `loop`, `stream`), for the five readers that take a window's
deltas of them. Pure functions, no JAX.

The engine counts every duration on one set of upper edges in ms
(`edges_ms`: steps of 0.1 ms to 20 ms, then doubling to 20.48 s): bucket i
holds [edges[i-1], edges[i]), one more bucket above the last edge. A
program older than these counters has no such keys and every function
here gives None.
"""

from __future__ import annotations

from typing import Sequence


def window_stats(observed: dict, key: str) -> tuple[dict, dict] | None:
    """(after, before) of `engine_stats()[key]` at the window's edges."""
    if not observed.get("before") or not observed.get("after"):
        return None
    after = observed["after"]["stats"].get(key)
    before = observed["before"]["stats"].get(key)
    if after is None or before is None:
        return None
    return after, before


def rose(after: Sequence[int], before: Sequence[int]) -> list[int]:
    """Bucket counts of the window: after - before."""
    return [a - b for a, b in zip(after, before)]


def pooled(counts: Sequence[Sequence[int]]) -> list[int]:
    return [sum(col) for col in zip(*counts)]


def bucket_bounds(edges: Sequence[float], i: int) -> tuple[float, float]:
    """[lower, upper) of bucket i; the bucket above the last edge is taken
    to end at twice that edge."""
    lower = edges[i - 1] if i else 0.0
    upper = edges[i] if i < len(edges) else 2 * edges[-1]
    return lower, upper


def percentile_bucket(counts: Sequence[int], q: float
                      ) -> tuple[int, float] | None:
    """(the bucket that holds the q-th percentile, 0..100, how far into the
    bucket's counts it lies, 0..1); None over no counts."""
    total = sum(counts)
    if total <= 0:
        return None
    rank = total * q / 100.0
    below = 0
    for i, n in enumerate(counts):
        if n and below + n >= rank:
            return i, (rank - below) / n
        below += n
    return None  # unreachable: rank <= total


def percentile(counts: Sequence[int], edges: Sequence[float], q: float
               ) -> float | None:
    """The q-th percentile read off bucket counts, interpolated linearly
    inside its bucket."""
    found = percentile_bucket(counts, q)
    if found is None:
        return None
    i, share = found
    lower, upper = bucket_bounds(edges, i)
    return lower + share * (upper - lower)


def gaps_by_cause(observed: dict) -> tuple[dict, list] | None:
    """({cause: the window's bucket counts}, edges) of `token_gaps`."""
    found = window_stats(observed, "token_gaps")
    if found is None:
        return None
    after, before = found
    return ({cause: rose(counts, before["by_cause"][cause])
             for cause, counts in after["by_cause"].items()},
            after["edges_ms"])


def turns(observed: dict) -> tuple[dict, list] | None:
    """({kind of step read: {"count", "wall_s", "hist"} of the window},
    edges) of `loop.turns`; the edges are `token_gaps`'s."""
    found = window_stats(observed, "loop")
    edges = window_stats(observed, "token_gaps")
    if found is None or edges is None:
        return None
    after, before = found
    out = {}
    for kind, t in after["turns"].items():
        b = before["turns"][kind]
        out[kind] = {"count": t["count"] - b["count"],
                     "wall_s": t["wall_s"] - b["wall_s"],
                     "hist": rose(t["hist"], b["hist"])}
    return out, edges[0]["edges_ms"]
