"""Of the window's token gaps at or above the bucket that holds their 95th
percentile, the share that had a prefill step (a whole prompt or a chunk, of
any request) read between the two tokens: whether the tail is the newcomer's
chunk or the decode program. Prints the window's gaps by cause."""
from benchmark import gap_account
from benchmark.readers import counter_delta, prom_sum


def read(observed):
    found = gap_account.gaps_by_cause(observed)
    if found is None:
        return None
    by_cause, edges = found
    everything = gap_account.pooled(list(by_cause.values()))
    at = gap_account.percentile_bucket(everything, 95)
    if at is None:
        return None
    tail = {cause: sum(counts[at[0]:]) for cause, counts in by_cause.items()}
    _print_account(observed, by_cause, everything, edges, tail)
    return 100.0 * tail.get("after_prefill", 0) / sum(tail.values())


def _print_account(observed, by_cause, everything, edges, tail):
    def page_delta(name):
        a = prom_sum(observed["after"]["page"], name)
        b = prom_sum(observed["before"]["page"], name)
        return None if a is None else a - (b or 0.0)

    after, before = gap_account.window_stats(observed, "token_gaps")
    sums = {c: after["sum_ms"][c] - before["sum_ms"][c] for c in by_cause}
    print("[gaps] cause: gaps, mean ms, p50, p95, of the tail | " + "; ".join(
        f"{c}: {sum(n)}, {sums[c] / max(1, sum(n)):.3f}, "
        f"{gap_account.percentile(n, edges, 50) or 0:.2f}, "
        f"{gap_account.percentile(n, edges, 95) or 0:.2f}, {tail[c]}"
        for c, n in by_cause.items()), flush=True)
    # every token is its request's first, one of a verify dispatch's run,
    # or has a gap; the page is read a few ms after the stats, so the two
    # sides may differ by what one or two steps emit
    burst = after["burst"] - before["burst"]
    first = page_delta("serve_llm_ttft_ms_count")
    tokens = page_delta("serve_llm_tokens_generated_total")
    print(f"[gaps] pooled p50 "
          f"{gap_account.percentile(everything, edges, 50):.2f} p95 "
          f"{gap_account.percentile(everything, edges, 95):.2f} p99 "
          f"{gap_account.percentile(everything, edges, 99):.2f} ms; "
          f"identity: gaps {sum(everything)} + burst {burst} + first "
          f"tokens {first} against tokens emitted {tokens}; preemptions "
          f"{counter_delta(observed, 'preemptions')}", flush=True)
