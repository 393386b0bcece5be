"""The index pass's and the top-k's share of their roofline in the traced
window, the XLA operations' (no kernel was written): the least time the
chip could take for the indexer-key bytes below the lanes' lengths and
the 32 x 128 products over them (`benchmark/flops_dsa.py`; the top-k has
no operations or bytes of its own to require: its time counts against the
pass), over the device time of the operations that do both
(`dsa_ops.latent_ops`).

The trace says which programs ran (`dsa_ops.programs`); what they had to
score is the window's mean a program, from
`engine_stats()["context_by_kind"]` (`dsa_ops.counters`): d`slots_valid` a
decode lane and step, and a chunk launch (launches = d`slots_full` / the
slots a table holds). `slots_scored`, which is what the program read
(whole tiles, a group's longest lane), is its choice and is not what is
required. Bound by memory in decode and by the products in a chunk."""
from benchmark import dsa_ops


def read(observed):
    events = observed.get("events")
    found = dsa_ops.latent_ops(events, observed["config"]) if events else None
    if not found or not found["index"]:
        return None
    took = found["topk"] + sum(s for s, _ in found["index"].values())
    best = dsa_ops.least_seconds(observed, found, 0)
    return 100.0 * best / took if took > 0 and best else None
