"""Operations and bytes the read of a lane's cached context REQUIRES:
for every layer of a kind, the K and V rows of the cached slots some row
of the program can see, read once, and the score and value products of
every (row, visible slot) pair over all H query heads. Slots read beyond
a lane's length or outside the window (whole tiles, a group's longest
lane) are the program's choice and do not count; nor do the program's own
rows, which are no cached context.
"""

from __future__ import annotations

from benchmark.flops import least_seconds


def ctx_flops(pairs: float, n_head: int, head_dim: int,
              v_head_dim: int) -> float:
    """`pairs` (row, visible cached slot) pairs of one layer: q.k over
    `head_dim` and p.v over `v_head_dim`, every query head."""
    return pairs * 2.0 * n_head * (head_dim + v_head_dim)


def ctx_bytes(slots: float, row_bytes: int) -> float:
    """`slots` visible cached slots of one layer, K and V rows once."""
    return slots * float(row_bytes)


def window_pairs(rows: int, visible: float) -> float:
    """Pairs of a window kind's program of `rows` rows whose first row
    sees `visible` cached slots: each later row sees one fewer."""
    n = min(rows, int(visible))
    return n * visible - n * (n - 1) / 2.0


def context_counters(observed: dict, kv: str, program: str) -> dict | None:
    """after - before of `engine_stats()["context_by_kind"][kv][program]`
    inside the window. None where the program has no such counters (a
    parent without kinds of KV layer)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    a = (observed["after"]["stats"].get("context_by_kind") or {}).get(kv)
    b = (observed["before"]["stats"].get("context_by_kind") or {}).get(kv)
    if not a or program not in a:
        return None
    b = (b or {}).get(program) or {}
    return {k: v - b.get(k, 0) for k, v in a[program].items()}


def program_least_seconds(kinds: list[dict], visible: dict, rows: int,
                          lanes: int, cfg: dict, device_kind: str) -> float:
    """The least time the chip could take for the context reads of ONE
    program of `lanes` lanes of `rows` rows each, all kinds and layers:
    `visible[kind]` is the mean visible cached slots a lane."""
    flops = nbytes = 0.0
    for k in kinds:
        v = visible[k["name"]]
        pairs = rows * v if k["window"] is None else window_pairs(rows, v)
        flops += k["layers"] * lanes * ctx_flops(
            pairs, cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"])
        nbytes += k["layers"] * lanes * ctx_bytes(v, k["row_bytes"])
    return least_seconds(flops, nbytes, device_kind)[0]
