"""Slots a row can attend after its indexer's choice, over the cached
slots it could see without one, in the window: 100 x d`slots_selected` /
d`slots_valid` of the latent kind of KV layer
(`engine_stats()["context_by_kind"]`, decode steps and chunks together;
computed on the host from the lanes' lengths: `select` or the lane's
length, whichever is less, over the lane's length). Lower is sparser; 100
means no lane's context passed `index_topk` in the window. None where the
program has no kind that selects."""
from benchmark import dsa_ops


def read(observed):
    moved = dsa_ops.counters(observed)
    if not moved:
        return None
    valid = sum(c["slots_valid"] for c in moved.values())
    chosen = sum(c["slots_selected"] for c in moved.values())
    return 100.0 * chosen / valid if valid > 0 else None
