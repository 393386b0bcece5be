"""The dense latent read's share of its roofline in the traced window, the
XLA operations' (no kernel was written): the least time the chip could
take for every (row, cached slot) pair's per-head products (32 heads x 2
x 320 operations: the up-projected form's, which no form undercuts) and
for each cached latent row read once a program and layer (576 lanes, the
padding not counted), the greater of the two at the chip's peaks
(`benchmark/flops_mla_dense.py`), over the device time of the loops that
do it (`mla_dense_ops.dense_ops`). The trace says which programs ran, the
window's counters what a program of each kind had to read. The program
reads ABSORBED (every head on the one 640-lane row: 3.4 times the
per-head products), so a chunk's share cannot pass 29% and shows that
choice; a decode step is bound by memory."""
from benchmark import mla_dense_ops


def read(observed):
    events = observed.get("events")
    found = mla_dense_ops.dense_ops(events, observed["config"]) \
        if events else None
    if not found:
        return None
    took = mla_dense_ops.seconds(found)
    best = mla_dense_ops.least_seconds(observed, found)
    return 100.0 * best / took if took > 0 and best else None
