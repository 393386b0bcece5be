"""Streamed items a `stream_items` message of the window: how many of a
step's token events left the replica together (`engine_stats()["stream"]`,
d`pushed` / d`messages`; the engine's loop sends what a step put down as one
message an owner). 1 is an item a message, as a pulled stream sends them; `items - pushed` of the same
account is what still went that way. A program without the two counters
gives None."""
from benchmark import gap_account


def read(observed):
    found = gap_account.window_stats(observed, "stream")
    if found is None:
        return None
    after, before = found
    if "pushed" not in after or "pushed" not in before:
        return None
    pushed = after["pushed"] - before["pushed"]
    messages = after["messages"] - before["messages"]
    items = after["items"] - before["items"]
    print(f"[stream] {pushed} of {items} items pushed in {messages} "
          f"messages", flush=True)
    if messages <= 0:
        return None
    return pushed / messages
