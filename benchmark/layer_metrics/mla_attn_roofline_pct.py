"""The latent attention core's share of its roofline in the traced
window, the XLA operations' (no kernel was written): the least time the
chip could take for the latent rows the rows attend after the choice
(576 lanes each, the padding not counted) and every head's score and
value products over them (`benchmark/flops_dsa.py`), over the device time
of the operations that do it (`dsa_ops.latent_ops`: the fold of the
lanes' latent tiles under the choice, a chunk's and a decode step's).

As `dsa_index_roofline_pct`: the trace says which programs ran, the
window's counters what a program of each kind had to attend
(d`slots_selected` a decode lane and step, and a chunk launch). A chunk's
256 rows are taken to attend the same slots, the fewest bytes that could
serve them, so its share is a floor. A decode step is bound by memory
(2,048 rows of 1,152 B a lane and layer), a chunk by the products."""
from benchmark import dsa_ops


def read(observed):
    events = observed.get("events")
    found = dsa_ops.latent_ops(events, observed["config"]) if events else None
    if not found or not found["core"]:
        return None
    took = sum(s for s, _ in found["core"].values())
    best = dsa_ops.least_seconds(observed, found, 1)
    return 100.0 * best / took if took > 0 and best else None
