"""What a decode step's fetch spends AFTER the program's first result (the
sampled ids, a few bytes) is on the host: the copy of the logits rows and a
routed family's pairs, and the rows put back in the caller's order (a second
copy of them on the host where the orders differ):
(d`fetch["decode"].copy_s` + d`order_s`) / d`steps["decode"]` of
`engine_stats()` over the window. The wait for the program is `wait_s` of the
same record; `decode_d2h_kb_per_step` is the copy's size. What returning one
float a lane would take out (ROADMAP S6 (b), D7). A program without the
counter gives None."""
from benchmark import gap_account
from benchmark.readers import counter_delta


def read(observed):
    found = gap_account.window_stats(observed, "fetch")
    steps = counter_delta(observed, "steps", "decode")
    if found is None or not steps:
        return None
    after, before = (n["decode"] for n in found)
    return 1e3 * (after["copy_s"] + after["order_s"]
                  - before["copy_s"] - before["order_s"]) / steps
