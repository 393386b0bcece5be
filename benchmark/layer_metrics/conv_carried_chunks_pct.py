"""Of the window's prefill programs of a family whose lanes carry a
recurrent state (a conv window, an SSM state), the share that started from
the state an earlier chunk of their sequence left in the lane's slot and
not from zero: 100 x d`carried` / (d`carried` + d`resets`) of
`engine_stats()["state"]`. A prompt of n chunks runs one fresh program and
n - 1 carried ones, so the share says how much of the prefill work leans on
the carried rows being right. A program older than the counter, or a
family with no state, has no such key and this gives None."""
from benchmark.readers import counter_delta


def read(observed):
    carried = counter_delta(observed, "state", "carried")
    fresh = counter_delta(observed, "state", "resets")
    if carried is None or fresh is None \
            or "carried" not in observed["after"]["stats"].get("state", {}) \
            or carried + fresh <= 0:
        return None
    return 100.0 * carried / (carried + fresh)
