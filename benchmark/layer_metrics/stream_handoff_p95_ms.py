"""95th percentile, over the window's streamed items, of the replica's half
of the hand-off to the client (`engine_stats()["stream"]`): pickup (the loop
put the item down -> the request thread took it up) plus ship (the request
thread serialising and sending it, until it asks for the next). The wire and
the client's `get` are not in it."""
from benchmark import gap_account


def read(observed):
    found = gap_account.window_stats(observed, "stream")
    if found is None:
        return None
    after, before = found
    items = after["items"] - before["items"]
    if items > 0:
        print(f"[stream] {items} items: pickup mean "
              f"{1e3 * (after['pickup_s'] - before['pickup_s']) / items:.4f}"
              f" ms, ship mean "
              f"{1e3 * (after['ship_s'] - before['ship_s']) / items:.4f} ms,"
              f" longest hand-off since start {after['max_ms']:.2f} ms",
              flush=True)
    return gap_account.percentile(
        gap_account.rose(after["handoff"], before["handoff"]),
        after["edges_ms"], 95)
