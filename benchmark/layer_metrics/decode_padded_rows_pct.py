"""Of the rows the window's decode programs ran, the share no lane owned:
100 x (rows - lanes) / rows, the rows summed over the steps by their
program's rows (`benchmark/decode_steps.py`). A padded row costs a step
what a lane's does in every product, and its slot is written back as read.
With `decode_lanes_per_step` it says which programs ran: 24 lanes a step
at 25% are 32-row programs."""
from benchmark import decode_steps


def read(observed):
    found = decode_steps.in_window(observed)
    if found is None:
        return None
    steps, lanes = found
    rows = sum(r * n for r, n in steps.items())
    return 100.0 * (rows - lanes) / rows
