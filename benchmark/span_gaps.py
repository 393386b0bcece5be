"""The first device's idle time, by what the serve engine's step loop was
doing in it.

The engine writes its phases into the profiler's own trace as host events
(`ray_tpu.util.tracing.annotate`): `llm.step.decode` / `llm.step.prefill`
around one step's work, inside them `llm.prepare`, `llm.dispatch`,
`llm.fetch`, `llm.commit`, `llm.emit`, `llm.bookkeep`, between steps
`llm.schedule` and `llm.idle`. They are on the clock of the device
operations, so every instant the device idles falls in one step's interval
(from the previous step's end to its own end) and under at most one phase:

- `fetch`: the host waits in `np.asarray` and the device has nothing left
  to run, i.e. the copy to the host after the program ended;
- `dispatch`: the jitted call has not yet put the program on the device;
- `idle`: the engine had no work (not part of a step's gap);
- `host`: everything else, the Python the device waits out (schedule,
  prepare, commit, emit, bookkeep and what lies between them).

The profiler converts the device's own timestamps to the host's clock, and
that conversion is good to about a millisecond, not better: a trace can show
a program that starts before the host began to dispatch it (my chip runs,
PR 25: by up to 0.6 ms, in two captures of eight). `clock_check` says how far a
trace breaks causality, and `split` moves the device's timeline by just that
much first. The whole gap and its `host` part do not depend on it; how the
rest divides between `fetch` and `dispatch` does, by as much as the clocks
are apart.

Pure functions over events `(plane, line, name, start_ns, dur_ns)`; a trace
without `llm.step.*` events (a program older than the annotations) gives
None everywhere.
"""

from __future__ import annotations

import bisect
from collections import Counter

from benchmark import trace_reduce

STEP = "llm.step."
PARTS = ("fetch", "dispatch", "idle")  # the rest of a gap is `host`
# Device operations closer together than this are one program: inside a
# program they follow each other within microseconds, between two programs
# the device waits for the host for milliseconds.
PROGRAM_GAP_NS = 100_000


def step_line(events) -> tuple[str, str] | None:
    """(plane, line) of the host thread that steps the engine: the one
    with the most `llm.step.*` events."""
    lines = Counter((e.plane, e.line) for e in events
                    if e.name.startswith(STEP))
    return lines.most_common(1)[0][0] if lines else None


def _covered(spans: list[tuple[float, float]], starts: list[float],
             a: float, b: float) -> float:
    """Length of [a, b] covered by `spans` (sorted, disjoint)."""
    total = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(spans) and spans[i][0] < b:
        total += max(0.0, min(b, spans[i][1]) - max(a, spans[i][0]))
        i += 1
    return total


def split(events) -> dict | None:
    """Every whole step with its share of the first device's idle time:

        {"steps": [{"kind", "start_ns", "end_ns", "fetch_ns",
                    "dispatch_ns", "host_ns", "idle_ns"}, ...],
         "edges_ns": idle time before the first whole step's interval and
                     after the last one's (the steps the window cut),
         "total_ns": all idle time between the device's first and last
                     operation,
         "shift_ns": how far the device's timeline was moved first}

    A step's interval runs from the previous step's end to its own end, so
    the first step seen has none and is left out. total_ns = edges_ns +
    the four parts summed over the steps."""
    events = list(events)
    line = step_line(events)
    planes = trace_reduce.device_planes(events)
    if line is None or not planes:
        return None
    mine = sorted((e for e in events if (e.plane, e.line) == line
                   and e.name.startswith("llm.")),
                  key=lambda e: e.start_ns)
    seen = [e for e in mine if e.name.startswith(STEP)]
    parts = {}
    for part in PARTS:
        spans = [(e.start_ns, e.start_ns + e.dur_ns) for e in mine
                 if e.name == "llm." + part]
        parts[part] = (spans, [s for s, _ in spans])
    busy = trace_reduce.busy_intervals(events, planes[0])
    check = clock_check(events, busy)
    shift = check["shift_ns"] if check else 0.0
    busy = [(a + shift, b + shift) for a, b in busy]
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] > busy[i][1]]
    total = sum(b - a for a, b in gaps)
    steps = []
    for prev, step in zip(seen, seen[1:]):
        a, b = prev.start_ns + prev.dur_ns, step.start_ns + step.dur_ns
        rec = {"kind": step.name[len(STEP):], "start_ns": a, "end_ns": b,
               **{p + "_ns": 0.0 for p in (*PARTS, "host")}}
        i = bisect.bisect_right(gaps, (a, float("inf")))
        if i and gaps[i - 1][1] > a:
            i -= 1  # a gap that began in the step before reaches into this
        while i < len(gaps) and gaps[i][0] < b:
            g0, g1 = max(a, gaps[i][0]), min(b, gaps[i][1])
            left = g1 - g0
            for part, (spans, starts) in parts.items():
                covered = _covered(spans, starts, g0, g1)
                rec[part + "_ns"] += covered
                left -= covered
            rec["host_ns"] += left
            i += 1
        steps.append(rec)
    inside = sum(r[p + "_ns"] for r in steps for p in (*PARTS, "host"))
    return {"steps": steps, "edges_ns": total - inside, "total_ns": total,
            "shift_ns": shift}


def mean_gap_ms(observed: dict, kind: str, part: str | None = None
                ) -> float | None:
    """Mean over the whole steps of `kind` ("decode", "prefill") of the
    device's idle time inside the step's interval, `llm.idle` left out;
    with `part` ("fetch", "dispatch", "host") only that part of it. The
    split is kept on `observed`: six readers ask for it."""
    if not observed.get("events"):
        return None
    if "span_gaps" not in observed:
        observed["span_gaps"] = split(observed["events"])
    found = observed["span_gaps"]
    steps = [r for r in (found or {}).get("steps", ()) if r["kind"] == kind]
    if not steps:
        return None
    names = (part,) if part else ("fetch", "dispatch", "host")
    return sum(r[p + "_ns"] for r in steps for p in names) / len(steps) / 1e6


def clock_check(events, busy=None) -> dict | None:
    """Are the annotations and the device operations on one clock? A
    program cannot start before the `llm.dispatch` that launches it began,
    nor end after the `llm.fetch` that reads its results returned. Over
    the dispatch/fetch pairs whose program the trace holds:

        {"programs": pairs checked,
         "starts_early_ns": the most a program starts before its dispatch
                            (negative: none does),
         "ends_late_ns": the most a program ends after its fetch
                         (negative: none does),
         "shift_ns": the least move of the device's timeline that makes
                     every pair causal (0 when it already is, and when no
                     move can: both of the above positive)}

    `busy`: the first device's busy intervals, where the caller has
    them."""
    events = list(events)
    line = step_line(events)
    planes = trace_reduce.device_planes(events)
    if line is None or not planes:
        return None
    if busy is None:
        busy = trace_reduce.busy_intervals(events, planes[0])
    mine = sorted((e for e in events if (e.plane, e.line) == line
                   and e.name in ("llm.dispatch", "llm.fetch")),
                  key=lambda e: e.start_ns)
    programs: list[list[float]] = []
    for a, b in busy:
        if programs and a - programs[-1][1] < PROGRAM_GAP_NS:
            programs[-1][1] = b
        else:
            programs.append([a, b])
    starts = [p[0] for p in programs]
    early, late, checked = float("-inf"), float("-inf"), 0
    for d, f in zip(mine, mine[1:]):
        if d.name != "llm.dispatch" or f.name != "llm.fetch":
            continue
        d0, f1 = d.start_ns, f.start_ns + f.dur_ns
        # a program lasts tens of ms and the clocks differ by about one:
        # the pair's program is the one running at the pair's midpoint
        i = bisect.bisect_right(starts, (d0 + f1) / 2) - 1
        if i < 0 or programs[i][1] < (d0 + f1) / 2:
            continue
        checked += 1
        early = max(early, d0 - programs[i][0])
        late = max(late, programs[i][1] - f1)
    if not checked:
        return None
    shift = 0.0
    if early > 0 >= late:
        shift = early
    elif late > 0 >= early:
        shift = -late
    return {"programs": checked, "starts_early_ns": early,
            "ends_late_ns": late, "shift_ns": shift}
