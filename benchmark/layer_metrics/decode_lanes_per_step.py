"""Lanes a decode step of the window moved one token on: d`decode_lanes`
/ d(steps) of `engine_stats()["state"]` (`benchmark/decode_steps.py`).
`batch_occupancy` says how many lanes hold a sequence; this says how many
of them DECODE in a step, which is what shares a step's read of the whole
tree: with one chunk between two decode steps a lane holds its slot while
it waits for its prompt's chunks, and the steps run short (ROADMAP S7)."""
from benchmark import decode_steps


def read(observed):
    found = decode_steps.in_window(observed)
    if found is None:
        return None
    steps, lanes = found
    return lanes / sum(steps.values())
