"""Whether a serve run's window stood still: one line a run, traced or not.

`line(...)` gathers, from the engine's own always-on account
(`engine_stats()`: `loop.turns`, `token_gaps`) at the window's edges and at
the run's end (and the replica's half of the streams' hand-off, `stream`),
and from the harness's side (the generator's lateness, a 20 ms ticker's longest
silence), what tells a run in which the loop or the machine stopped from one
that merely read high, and how many of the pool's pages the window's
sequences held (`kv_pages`: at its two edges, and the most over the traced
run's polls), under which of its phases the loop spent the window
(`phase_s`: where a turn of seconds lies, `fetch` being the wait for the
device) and how many programs were compiled inside it (`compiled`: 0 in
every sound run). `report` prints it as `[stall] {json}` (in the traced run also
the gaps' histogram by cause) and `parse` reads such a line back. It changes
no metric.
Pure functions but for `Ticker`, no JAX.
"""

from __future__ import annotations

import json
import threading
import time

from benchmark import gap_account

PREFIX = "[stall] "
FIELDS = ("turns", "gaps", "engine_itl_p95_ms", "handoff",
          "kv_pages", "phase_s", "compiled", "generator_late_ms",
          "ticker_max_ms", "ticker_over_100ms")
TICK_S = 0.02
HIST_STEP_MS = 0.5


class Ticker:
    """A thread of the harness's own process that wakes every 20 ms
    between `start` and `stop` and keeps the longest time between two
    wake-ups: what the machine, or this process's interpreter lock, kept
    from every thread here, the request threads' clocks included."""

    def __init__(self, t_from: float, t_to: float):
        self.t_from, self.t_to = t_from, t_to
        self.max_ms, self.over_100ms, self.ticks = 0.0, 0, 0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> "Ticker":
        self._thread.start()
        return self

    def _run(self):
        time.sleep(max(0.0, self.t_from - time.monotonic()))
        last = time.monotonic()
        while last < self.t_to:
            time.sleep(TICK_S)
            now = time.monotonic()
            silent_ms = (now - last) * 1e3
            self.max_ms = max(self.max_ms, silent_ms)
            self.over_100ms += silent_ms > 100.0
            self.ticks += 1
            last = now

    def join(self):
        self._thread.join(timeout=max(1.0, self.t_to - time.monotonic() + 1))


def _window(before: dict, after: dict) -> dict:
    """The two edges as `gap_account`'s window readers take them."""
    return {"before": {"stats": before}, "after": {"stats": after}}


def _percentiles(counts, edges) -> dict:
    return {f"p{q}": gap_account.percentile(counts, edges, q)
            for q in (50, 95, 99)}


def kv_pages(snapshots: list[dict]) -> dict | None:
    """Pages of each kind's pool that sequences held in `snapshots`
    (`engine_stats()` taken inside the window): {kind: {"total", "first",
    "last", "most"}}. What a cell fills of its pool, as against what the
    configuration reserves."""
    held: dict[str, dict] = {}
    for snap in snapshots:
        for kind, pool in snap.get("kv", {}).items():
            used = pool["pages_used"]
            h = held.setdefault(kind, {"total": pool["pages_total"],
                                       "first": used, "most": used})
            h["last"], h["most"] = used, max(h["most"], used)
    return held or None


def line(before: dict | None, after: dict | None, final: dict,
         late_s: float, ticker: Ticker | None,
         polls: list[dict] | None = None) -> dict:
    """`before` / `after`: `engine_stats()` at the window's edges (None
    where they were not taken: the gaps and a turn's `window_top_ms` are
    then left out); `final`: at the run's end, drained; `polls`: the traced
    run's readings between the edges."""
    turns = {}
    for kind, t in final["loop"]["turns"].items():
        turns[kind] = {"count": t["count"], "max_ms": t["max_ms"],
                       "over_250ms_s": t["over_250ms_s"]}
    out = {"turns": turns, "gaps": None, "engine_itl_p95_ms": None,
           "handoff": None, "kv_pages": None, "phase_s": None,
           "compiled": None,
           "generator_late_ms": late_s * 1e3,
           "ticker_max_ms": ticker.max_ms if ticker else None,
           "ticker_over_100ms": ticker.over_100ms if ticker else None}
    if not before or not after or "token_gaps" not in after:
        return out
    out["kv_pages"] = kv_pages([before, *(polls or []), after])
    # the loop's seconds by phase over the window, and the programs first
    # compiled inside it: what a turn of seconds was spent on
    was = before.get("step_phase_seconds", {})
    out["phase_s"] = {phase: s - was.get(phase, 0.0) for phase, s in
                      after.get("step_phase_seconds", {}).items()} or None
    if "compiled_programs" in after and "compiled_programs" in before:
        out["compiled"] = after["compiled_programs"] \
            - before["compiled_programs"]
    observed = _window(before, after)
    by_kind, edges = gap_account.turns(observed)
    for kind, t in by_kind.items():
        top = [i for i, n in enumerate(t["hist"]) if n > 0]
        turns[kind]["window_count"] = t["count"]
        turns[kind]["window_top_ms"] = \
            gap_account.bucket_bounds(edges, max(top))[1] if top else None
        turns[kind]["window_over_250ms_s"] = \
            after["loop"]["turns"][kind]["over_250ms_s"] \
            - before["loop"]["turns"][kind]["over_250ms_s"]
    by_cause, edges = gap_account.gaps_by_cause(observed)
    pooled = gap_account.pooled(list(by_cause.values()))
    gaps = {cause: {"n": sum(n), **_percentiles(n, edges),
                    "max_ms": after["token_gaps"]["max_ms"][cause]}
            for cause, n in by_cause.items() if sum(n)}
    gaps["pooled"] = {"n": sum(pooled), **_percentiles(pooled, edges)}
    out["gaps"] = gaps
    out["engine_itl_p95_ms"] = gaps["pooled"]["p95"]
    # the replica's half of the streams' hand-off to the client: what the
    # client's gaps carry beyond the engine's
    sent, was = after.get("stream"), before.get("stream")
    if sent and was and sent["items"] > was["items"]:
        items = sent["items"] - was["items"]
        rose = gap_account.rose(sent["handoff"], was["handoff"])
        out["handoff"] = {
            "items": items,
            "pickup_mean_ms": 1e3 * (sent["pickup_s"] - was["pickup_s"])
            / items,
            "ship_mean_ms": 1e3 * (sent["ship_s"] - was["ship_s"]) / items,
            "p95_ms": gap_account.percentile(rose, sent["edges_ms"], 95),
            "p99_ms": gap_account.percentile(rose, sent["edges_ms"], 99)}
    return out


def histogram(before: dict, after: dict, step_ms: float = HIST_STEP_MS
              ) -> dict:
    """The window's token gaps by cause in bins of `step_ms`:
    {cause: [[bin's lower edge in ms, gaps], ...]}, empty bins left out."""
    by_cause, edges = gap_account.gaps_by_cause(_window(before, after))
    out = {}
    for cause, counts in by_cause.items():
        bins: dict[float, int] = {}
        for i, n in enumerate(counts):
            if n:
                lower = gap_account.bucket_bounds(edges, i)[0]
                key = round(step_ms * int(lower / step_ms + 1e-9), 3)
                bins[key] = bins.get(key, 0) + n
        if bins:
            out[cause] = sorted(bins.items())
    return out


def report(before, after, final, late_s, ticker, polls=None,
           hist: bool = False) -> dict:
    """Print the run's one stall line (and with `hist`, the traced run, the
    window's gaps by cause as a histogram); returns the line's object."""
    out = line(before, after, final, late_s, ticker, polls)
    if hist and out["gaps"] is not None:
        for cause, bins in histogram(before, after).items():
            print(f"[gaps-hist] {cause} ({HIST_STEP_MS} ms bins, lower "
                  "edge:gaps): " + " ".join(f"{lo:g}:{n}" for lo, n in bins),
                  flush=True)
    print(PREFIX + json.dumps(out), flush=True)
    return out


def parse(text: str) -> dict | None:
    """The stall line's object out of a run's output (its last one)."""
    found = None
    for row in text.splitlines():
        at = row.find(PREFIX)
        if at >= 0:
            found = json.loads(row[at + len(PREFIX):])
    return found
