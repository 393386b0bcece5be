"""Pages of the window kind's pool held by the running sequences, over
the pages the same sequences hold of the full kind's pool (what a window
kind that gave nothing back would hold: a full-length table a sequence),
mean of the window's polls of `engine_stats()["kv"]`. About 100 x (window
+ a chunk) / context while prefilling and 100 x window / context while
decoding."""


def read(observed):
    shares = []
    for stats in observed.get("polls") or []:
        kv = stats.get("kv") or {}
        window = [k for k in kv.values() if k.get("window") is not None]
        full = [k for k in kv.values() if k.get("window") is None]
        if not window or not full:
            return None
        held = sum(k["pages_used"] for k in full)
        if held:
            shares.append(100.0 * sum(k["pages_used"] for k in window)
                          / (held * len(window) / len(full)))
    return sum(shares) / len(shares) if shares else None
