"""Share of the device's busy time spent in the hyper-connected residual
path's coefficient maps: the product of a half-layer's normed state with
`phi` and the `mhc_maps` kernel (the maps and all 20 Sinkhorn iterations),
over the busy time of the first device (`benchmark/mhc_ops.py`). A floor
of the residual path's share: the state's mean square and the two mixes
are not told apart from their neighbours by any label (XLA fuses the
mixes into the products before and after them)."""
from benchmark import mhc_ops, mla_dense_ops


def read(observed):
    events = observed.get("events")
    found = mhc_ops.maps_ops(events, observed["config"]) if events else None
    if not found or not found["kernel"][1]:
        return None
    busy = mla_dense_ops.busy_seconds(events)
    return 100.0 * mhc_ops.seconds(found) / busy if busy > 0 else None
