"""Share of the device's busy time spent in the latent attention's core:
the fold of the lanes' latent tiles under the choice as their mask, a
chunk's and a decode step's (`dsa_ops.latent_ops`: one `while` a group of
lanes and layer; the projections, the fold of the queries onto the latent,
the softmax's start on the program's own rows and the value up-projection
are not counted), over the busy time of the first device."""
from benchmark import dsa_ops


def read(observed):
    events = observed.get("events")
    found = dsa_ops.latent_ops(events, observed["config"]) if events else None
    if not found or not found["core"]:
        return None
    busy = dsa_ops.busy_seconds(events)
    took = sum(s for s, _ in found["core"].values())
    return 100.0 * took / busy if busy > 0 else None
