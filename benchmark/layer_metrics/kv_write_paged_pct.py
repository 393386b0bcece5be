"""Of the valid K (and V) rows the window's programs stored in the pools,
the share stored a page at a time: 100 x paged / (paged + rowwise), every
kind of KV layer together, between the first and the last of the window's
polls of `engine_stats()["kv"]` (`rows_written_paged`,
`rows_written_rowwise`: a prompt's and a chunk's programs store whole
pages, decode and verify single rows). A program older than the counters
has no such keys and this gives None."""


def _rows(stats):
    kv = stats.get("kv") or {}
    if not kv or any("rows_written_paged" not in k for k in kv.values()):
        return None
    return (sum(k["rows_written_paged"] for k in kv.values()),
            sum(k["rows_written_rowwise"] for k in kv.values()))


def read(observed):
    polls = observed.get("polls") or []
    if len(polls) < 2:
        return None
    first, last = _rows(polls[0]), _rows(polls[-1])
    if first is None or last is None:
        return None
    paged, rowwise = last[0] - first[0], last[1] - first[1]
    if paged + rowwise <= 0:
        return None
    print(f"[kv] rows written in the window: {paged} a page at a time, "
          f"{rowwise} row by row", flush=True)
    return 100.0 * paged / (paged + rowwise)
