"""What the start-up readers share: the engine's own account of its start
(`engine_stats()["startup_seconds"]`, `["warmup_cache"]`) as the window's
last snapshot holds it. None from a program that keeps no such account."""

from __future__ import annotations


def startup(observed: dict, key: str = "startup_seconds") -> dict | None:
    return ((observed.get("after") or {}).get("stats") or {}).get(key)
