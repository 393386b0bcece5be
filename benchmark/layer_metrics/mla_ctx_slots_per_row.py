"""Mean cached slots a decode row attended over the window: a latent kind
with no indexer reads every one, so this is how long the contexts were
(`engine_stats()["context"]["decode"]`: `row_slots` over `rows`, counted
host-side from the lanes' lengths). None where the engine has no such
counters (a family without such a kind; the parent)."""


def read(observed):
    if not observed.get("before") or not observed.get("after"):
        return None
    moved = {}
    for what in ("rows", "row_slots"):
        a, b = ((observed[k]["stats"].get("context") or {}).get("decode", {})
                .get(what) for k in ("after", "before"))
        if a is None or b is None:
            return None
        moved[what] = a - b
    return moved["row_slots"] / moved["rows"] if moved["rows"] > 0 else None
