"""From a profiler trace to busy time, idle share, top operations and the
host's part in the idle gaps.

`reduce()` and its helpers are pure functions over events
`(plane, line, name, start_ns, dur_ns)`; `load()` turns the `.xplane.pb`
that `jax.profiler` wrote into such events with nothing but jax. A device
plane is one whose name starts with `/device:`; its operations are the
events of its `XLA Ops` line (the other lines — modules, steps — cover the
same time again and would count it twice).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Iterable, NamedTuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 20_000  # idle gaps shorter than this are not attributed
MAX_GAPS = 4000  # only the longest gaps are attributed


def load(trace_dir: str) -> list[Event]:
    """Every event of the newest `.xplane.pb` under `trace_dir`. The name of
    a device operation is shortened by `op_label`."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            ops = device and line.name == OPS_LINE
            for ev in line.events:
                name = ev.name
                if ops:
                    name = op_label(name)
                events.append(Event(plane.name, line.name, name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


CONTAINERS = ("while", "conditional", "call")  # their time is their bodies'


def op_label(text: str) -> str:
    """A device operation's event name is its whole HLO line. Shorten it to
    `name opcode result`, layouts dropped and tuples cut to four parts:
    `copy.43 copy bf16[36,512,16,20,64]`,
    `checkpoint.18 custom-call:tpu_custom_call (bf16[384,1024,64], bf16[384,1024,64])`.
    A name that is no HLO line is kept as it is."""
    m = re.match(r"%?(\S+) = (.*)$", text, re.S)
    if not m:
        return text
    name, rest = m.groups()
    op = re.search(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(", rest)
    if not op:
        return name
    result = re.sub(r"\{[^}]*\}", "", rest[:op.start() + 1])
    result = re.sub(r"/\*[^*]*\*/", "", result).strip()
    if result.startswith("("):
        parts = [p.strip() for p in result[1:-1].split(", ")]
        result = "(" + ", ".join(parts[:4]) + \
            (", ..." if len(parts) > 4 else "") + ")"
    opcode = op.group(1)
    if opcode == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', rest)
        if t:
            opcode += ":" + t.group(1)
    return f"{name} {opcode} {result}"


def opcode_of(label: str) -> str:
    parts = label.split(" ", 2)
    return parts[1].split(":")[0] if len(parts) > 1 else ""


def device_planes(events: Iterable[Event]) -> list[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)
                   and e.line == OPS_LINE})


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(events: Iterable[Event], plane: str
                   ) -> list[tuple[float, float]]:
    return _union([(e.start_ns, e.start_ns + e.dur_ns) for e in events
                   if e.plane == plane and e.line == OPS_LINE
                   and e.dur_ns > 0])


def op_seconds(events: Iterable[Event], pattern: str,
               plane: str | None = None) -> tuple[float, int]:
    """(seconds, count) of device operations whose label matches the
    regular expression, on one device plane (default: the first)."""
    events = list(events)
    planes = device_planes(events)
    if not planes:
        return 0.0, 0
    plane = plane or planes[0]
    rx = re.compile(pattern)
    hits = [e.dur_ns for e in events
            if e.plane == plane and e.line == OPS_LINE and rx.search(e.name)]
    return sum(hits) / 1e9, len(hits)


def reduce(events: Iterable[Event]) -> dict:
    """busy_s (mean over device planes), window_s, idle_share, the ten
    operations with most device time and the ten host events that covered
    most idle time on the first device."""
    events = list(events)
    planes = device_planes(events)
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                "devices": 0, "device_ops": [], "idle_gaps": []}
    per_plane = {p: busy_intervals(events, p) for p in planes}
    starts = [iv[0][0] for iv in per_plane.values() if iv]
    ends = [iv[-1][1] for iv in per_plane.values() if iv]
    if not starts:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                "devices": len(planes), "device_ops": [], "idle_gaps": []}
    t0, t1 = min(starts), max(ends)
    window = t1 - t0
    busy = sum(sum(e - s for s, e in iv) for iv in per_plane.values()) \
        / len(planes)
    by_op: dict[str, float] = defaultdict(float)
    for e in events:
        if e.plane == planes[0] and e.line == OPS_LINE \
                and opcode_of(e.name) not in CONTAINERS:
            by_op[e.name] += e.dur_ns
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "devices": len(planes),
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": attribute_gaps(events, per_plane[planes[0]]),
    }


def attribute_gaps(events: list[Event],
                   busy: list[tuple[float, float]]) -> list[list]:
    """Each idle gap of a device goes to the most specific host event that
    was running through it: the shortest one that overlaps at least half of
    the gap, else the one that overlaps it most. Sums by host event name,
    longest first, at most ten."""
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] >= MIN_GAP_NS]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:MAX_GAPS]
    host = sorted((e for e in events
                   if not e.plane.startswith(DEVICE_PREFIX)
                   and e.dur_ns > 0), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    longest = max((e.dur_ns for e in host), default=0.0)
    total: dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        best, best_key = None, None
        # host events that can overlap the gap start before its end and
        # no earlier than the longest event before its start
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_right(starts, ge)
        for e in host[lo:hi]:
            ov = min(ge, e.start_ns + e.dur_ns) - max(gs, e.start_ns)
            if ov <= 0:
                continue
            half = ov >= 0.5 * (ge - gs)
            key = (half, -e.dur_ns if half else ov)
            if best_key is None or key > best_key:
                best, best_key = e, key
        total[best.name if best else "unattributed"] += ge - gs
    top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v / 1e9] for k, v in top]
