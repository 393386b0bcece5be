"""A decode step's KDA recurrence against its roofline in the traced
window: the least time the chip could take for the state updates seen
(`flops_kda.step_least_seconds`: one step of the recurrence a lane, the
lane's state read once and written once, its q, k, v, g, beta rows) over
the device time the steps' operations took (`benchmark/kda_ops.py` tells
which).

A state update is one layer of one decode step, and its lanes are those
that DECODED in a step of the window (`benchmark/decode_steps.py`: the
slots the steps' lanes owned over the steps, never more than
`max_batch_size`): the slots no lane owned are the program's to touch,
not the algorithm's, and so is a state read again because a slice, a
reduce and an update did not fuse."""
from benchmark import decode_steps, flops_kda, kda_ops


def read(observed):
    found = kda_ops.from_observed(observed)
    counted = decode_steps.in_window(observed)
    if found is None or counted is None:
        return None
    s = kda_ops.sizes_of(observed["config"])
    took, updates = found["step"]
    if not updates or not took > 0:
        return None
    steps, owned = counted
    lanes = min(owned / sum(steps.values()), s["lanes"])
    least, _ = flops_kda.step_least_seconds(
        lanes, s["H"], s["d"], observed["device_kind"])
    return 100.0 * updates * least / took
