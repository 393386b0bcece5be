"""85th percentile of the time from a request's due time to its first
streamed token, over the requests due in the window (a failed one counts as
the worst). A per-layer metric and not an end-to-end one: with the ~90
requests a 51 s window holds it spreads by 9-13% between runs of one seed
(PERF.md, PR 24), more than any admissible bound could hold."""


def read(observed):
    return observed.get("ttft_p85_ms")
