"""The window's decode steps as the engine counted them, for a family
whose lanes carry a recurrent state (`engine_stats()["state"]`, written by
`StateSlots.note_decode`): `decode_steps`, a count of steps a program's
rows (a power of two up to `max_batch_size`), and `decode_lanes`, the
slots those steps' lanes owned, summed."""

from __future__ import annotations

from benchmark.readers import counter_delta


def in_window(observed: dict) -> tuple[dict[int, int], float] | None:
    """({rows of the decode program: steps}, lanes) over the window; None
    for a program older than the counter or a family with no state."""
    lanes = counter_delta(observed, "state", "decode_lanes")
    if lanes is None:
        return None
    after, before = (observed[k]["stats"].get("state", {}).get("decode_steps")
                     for k in ("after", "before"))
    if after is None:
        return None
    steps = {int(rows): n - (before or {}).get(rows, 0)
             for rows, n in after.items()}
    return (steps, lanes) if sum(steps.values()) > 0 else None
