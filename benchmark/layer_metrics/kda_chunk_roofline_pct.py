"""A prompt's and a chunk's KDA recurrence against its roofline in the
traced window: the least time the chip could take for the chunked form of
the programs seen (`flops_kda.chunk_least_seconds`: the larger of the
products the algorithm needs at its block size, held to the bf16 peak, and
the lane's state read once and written once with the rows' inputs and
outputs) over the device time their operations took (`benchmark/kda_ops.py`
tells which).

A program's layer is counted by its one-lane write into the state buffer,
and its rows are the window's mean of real rows a prompt's or chunk's
program (`kda_ops.prefill_rows_a_program`, from the engine's counters):
padded rows are the program's, not the algorithm's."""
from benchmark import flops_kda, kda_ops


def read(observed):
    found = kda_ops.from_observed(observed)
    rows = kda_ops.prefill_rows_a_program(observed)
    if found is None or rows is None:
        return None
    s = kda_ops.sizes_of(observed["config"])
    took, writes = found["chunk"]
    if not writes or not took > 0:
        return None
    least, _ = flops_kda.chunk_least_seconds(
        rows, kda_ops.BLOCK, s["H"], s["d"], observed["device_kind"])
    return 100.0 * writes * least / took
