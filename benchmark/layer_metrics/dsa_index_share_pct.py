"""Share of the device's busy time spent choosing what to attend to: the
index pass over the lanes' indexer keys (`dsa_ops.latent_ops`: one tile
loop a group of lanes and layer) and the exact top-k of its scores (the
`conditional` that holds the search, and a decode step's compaction of
the choice to slots), over the busy time of the first device."""
from benchmark import dsa_ops


def read(observed):
    events = observed.get("events")
    found = dsa_ops.latent_ops(events, observed["config"]) if events else None
    if not found or not found["index"]:
        return None
    busy = dsa_ops.busy_seconds(events)
    took = found["topk"] + sum(s for s, _ in found["index"].values())
    return 100.0 * took / busy if busy > 0 else None
