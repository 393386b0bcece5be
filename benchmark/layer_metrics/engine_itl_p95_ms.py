"""95th percentile of the window's gaps between two tokens of one request,
taken where the engine's loop emits them (`engine_stats()["token_gaps"]`,
all causes pooled, interpolated inside its 0.1 ms bucket): `itl_p95_ms`
less the hand-off to the client."""
from benchmark import gap_account


def read(observed):
    found = gap_account.gaps_by_cause(observed)
    if found is None:
        return None
    by_cause, edges = found
    return gap_account.percentile(
        gap_account.pooled(list(by_cause.values())), edges, 95)
