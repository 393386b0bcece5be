"""Mean admission wait of the requests that finished inside the window."""
from benchmark.readers import counter_delta


def read(observed):
    waited = counter_delta(observed, "phase_seconds", "queue")
    finished = counter_delta(observed, "finished_requests")
    if waited is None or not finished:
        return None
    return 1e3 * waited / finished
