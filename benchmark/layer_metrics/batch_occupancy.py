"""Decode lanes in use over max_batch_size, polled twice a second."""


def read(observed):
    polls = observed.get("polls")
    if not polls:
        return None
    return 100.0 * sum(p["running"] / p["max_batch_size"] for p in polls) \
        / len(polls)
