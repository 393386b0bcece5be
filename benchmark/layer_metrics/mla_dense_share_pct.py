"""Share of the device's busy time spent in the dense read of the latent
kind: the fold of every cached latent tile of the lanes into the running
softmax, a chunk's and a decode step's (`mla_dense_ops.dense_ops`: one
`while` a group of lanes and layer; the projections, the fold of the
queries onto the latent, the softmax's start on the program's own rows
and the value up-projection are not counted), over the busy time of the
first device."""
from benchmark import mla_dense_ops


def read(observed):
    events = observed.get("events")
    found = mla_dense_ops.dense_ops(events, observed["config"]) \
        if events else None
    if not found:
        return None
    busy = mla_dense_ops.busy_seconds(events)
    return 100.0 * mla_dense_ops.seconds(found) / busy if busy > 0 else None
