"""What the per-layer readers share: deltas of the engine's counters, means
of the program's histograms off the Prometheus page, and device time of
operations in the trace. A reader with nothing to read returns None.

`observed` is what a kind's runner hands over: for serve cells the engine's
stats and the cluster metrics page at both edges of the window ("before",
"after"), the stats polled in between ("polls"), and for every traced cell
the trace's events ("events").
"""

from __future__ import annotations

import re

from benchmark import trace_reduce


def prom_sum(page: str, name: str, **tags) -> float | None:
    """Sum of the samples of `name` whose labels include `tags`."""
    total, seen = 0.0, False
    for line in page.splitlines():
        if not line.startswith(name) or line[len(name)] not in " {":
            continue
        labels = dict(re.findall(r'(\w+)="([^"]*)"', line))
        if all(labels.get(k) == v for k, v in tags.items()):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


def counter_delta(observed: dict, key: str, sub: str | None = None
                  ) -> float | None:
    """after - before of one of the engine's counters (engine_stats())."""
    if not observed.get("before") or not observed.get("after"):
        return None
    a, b = (observed[k]["stats"].get(key) for k in ("after", "before"))
    if sub is not None:
        a, b = (a or {}).get(sub, 0.0), (b or {}).get(sub, 0.0)
    if a is None or b is None:
        return None
    return a - b


def histogram_mean(observed: dict, name: str, **tags) -> float | None:
    """(sum after - sum before) / (count after - count before)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    d = {}
    for part in ("sum", "count"):
        a = prom_sum(observed["after"]["page"], f"{name}_{part}", **tags)
        b = prom_sum(observed["before"]["page"], f"{name}_{part}", **tags)
        if a is None:
            return None
        d[part] = a - (b or 0.0)
    return d["sum"] / d["count"] if d["count"] > 0 else None


def trace_ops(observed: dict, pattern: str) -> tuple[float, int] | None:
    """(seconds, count) of operations on the first device whose label
    matches `pattern`; None without a trace."""
    if not observed.get("events"):
        return None
    return trace_reduce.op_seconds(observed["events"], pattern)
