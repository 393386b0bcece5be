"""The longest turn of the engine's loop that ended inside the window: the
upper edge of the highest bucket of `engine_stats()["loop"]["turns"]` whose
count rose between the window's edges, over both kinds of step read. A sound
turn is under 25 ms in every cell; seconds here say the loop stood still."""
from benchmark import gap_account


def read(observed):
    found = gap_account.turns(observed)
    if found is None:
        return None
    by_kind, edges = found
    top = [i for t in by_kind.values()
           for i, n in enumerate(t["hist"]) if n > 0]
    if not top:
        return None
    for kind, t in by_kind.items():
        if t["count"]:
            print(f"[loop] {kind} turns: {t['count']}, mean "
                  f"{1e3 * t['wall_s'] / t['count']:.3f} ms, p50 "
                  f"{gap_account.percentile(t['hist'], edges, 50):.2f} p95 "
                  f"{gap_account.percentile(t['hist'], edges, 95):.2f} p99 "
                  f"{gap_account.percentile(t['hist'], edges, 99):.2f}",
                  flush=True)
    return gap_account.bucket_bounds(edges, max(top))[1]
