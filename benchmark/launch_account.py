"""A launch of the serve engine's step loop, split by what the host did in
it: the call's own argument handling, the transfers of the step's host
arrays, the runtime's execute.

The loop writes one `llm.dispatch` host event around every jitted call
(`ray_tpu/serve/llm/runner.py`, `launch_*` and `verify`). Inside it, on the clock the
device operations are on, jax writes `PjitFunction(<impl>)` around the call
and `DevicePut` around each host array it transfers, and the TPU runtime its
own events under them (`AllocateRawBuffer`, `Linearize`,
`PJRT_LoadedExecutable_Execute`, ...). A launch's KIND is the program's
(`_decode_impl`: decode; `_prefill_impl`, `_chunk_impl`: prefill;
`_verify_impl`: verify), not the `llm.step.*` turn the dispatch lies in:
since PR 31 a turn is named after the step it READS and holds the launch of
the step behind it.

A span's self time is its duration less what its children cover. Every
`llm.dispatch` interval of the loop thread's line is split into

- `args`: `llm.dispatch` outside `PjitFunction` (`wrapper`: the cache probe,
  the mesh context, the lock, the results kept) plus `PjitFunction`'s own
  self time (the tree of arguments flattened, the signature looked up, the
  results wrapped) and what lies under `ARGS`;
- `put`: self time of the events in `PUT` and of what lies under them;
- `execute`: the same for `EXECUTE`;
- `unlisted`: children of `PjitFunction` under none of the lists, by name,
  so that a jaxlib that renames an event shows here and no part falls
  silent.

The four sum to the interval. **The runtime's events are not on the loop
thread's line**: the TPU plugin keeps its own tracer, which does not know a
Python thread's name, so what it records of the loop thread lands on a line
of its own (named `""` in the traces of jaxlib 0.9.0 with libtpu 0.0.34)
beside `python3`'s. `runtime_line` finds it as the line that holds the most
`EXECUTE` events inside the loop's `PjitFunction` intervals (the execute
call runs on the calling thread; transfers may run on worker threads, whose
lines are not taken: what overlaps a launch from another thread is not the
launch's time). No such line: the loop's line alone is read and the
runtime's time shows as `PjitFunction`'s self time; the printed line says
which line was taken.

Pure functions over events `(plane, line, name, start_ns, dur_ns)`, in the
manner of `span_gaps.py`; a trace without `llm.step.*` events gives None
everywhere.
"""

from __future__ import annotations

import bisect
import json
from collections import Counter, defaultdict

from benchmark import gap_account, span_gaps

DISPATCH = "llm.dispatch"
CALL = "PjitFunction("
KIND_OF = {"_decode_impl": "decode", "_prefill_impl": "prefill",
           "_chunk_impl": "prefill", "_verify_impl": "verify"}
# The two lists (and jax's one event for the arguments), by the names the
# traces of PR 52's ledger lines show. An event under a listed one is the
# listed one's, whatever its name.
ARGS = ("ParseArguments",)
PUT = ("DevicePut", "Linearize", "XlaLinearize",
       "TpuClient::LinearizeIntoImpl", "tpu::System::TransferToDevice",
       "AllocateRawBuffer", "DeferredTpuAllocator::Allocate")
EXECUTE = ("PJRT_LoadedExecutable_Execute",
           "CommonPjRtLoadedExecutable::ExecutePrepare",
           "CommonPjRtLoadedExecutable::ExecuteHelperOnSingleDevice",
           "AllocateOutputBuffersWithInputReuse")
PARTS = ("args", "put", "execute", "unlisted")
_LISTED = {**dict.fromkeys(ARGS, "args"), **dict.fromkeys(PUT, "put"),
           **dict.fromkeys(EXECUTE, "execute")}


def kind_of(name: str) -> str | None:
    """The kind of program a `PjitFunction(<impl>)` event launches."""
    if not name.startswith(CALL):
        return None
    return KIND_OF.get(name[len(CALL):].rstrip(")"))


def _calls(events, line) -> list:
    return [e for e in events if (e.plane, e.line) == line
            and kind_of(e.name) is not None]


def runtime_line(events, line) -> tuple[str, str] | None:
    """(plane, line) on which the runtime wrote the loop thread's own
    calls: the line other than `line` with the most `EXECUTE` events that
    start inside a `PjitFunction` interval of `line`."""
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns)
                   for e in _calls(events, line))
    starts = [s for s, _ in spans]
    found: Counter = Counter()
    for e in events:
        if e.name not in EXECUTE or e.plane != line[0] or e.line == line[1]:
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < spans[i][1]:
            found[(e.plane, e.line)] += 1
    return found.most_common(1)[0][0] if found else None


def _split(inside: list, b: float) -> dict:
    """Self times inside one call's interval, which ends at `b`, by part;
    `inside`: the events that start in it, the call's own first."""
    out = {p + "_ns": 0.0 for p in PARTS}
    unlisted: dict[str, float] = defaultdict(float)
    # [end, part its self time goes to, part an unknown child inherits,
    #  name, self time so far, how far its children cover it]
    stack: list[list] = []

    def close(node):
        out[node[1] + "_ns"] += node[4]
        if node[1] == "unlisted":
            unlisted[node[3]] += node[4]

    for e in sorted(inside, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and e.start_ns >= stack[-1][0]:
            close(stack.pop())
        # a child that straddles its parent's end is cut there
        end = min(e.start_ns + e.dur_ns, stack[-1][0] if stack else b)
        if stack:
            parent = stack[-1]
            covered = max(0.0, end - max(e.start_ns, parent[5]))
            parent[4] -= covered
            parent[5] = max(parent[5], end)
        # the outermost listed event decides: an output buffer's
        # `AllocateRawBuffer` under `AllocateOutputBuffersWithInputReuse`
        # is the execute's, not a transfer's
        hands_on = stack[-1][2] if stack else None
        if e.name.startswith(CALL):
            part = "args"
        elif hands_on is None:
            hands_on = _LISTED.get(e.name)
            part = hands_on or "unlisted"
        else:
            part = hands_on
        stack.append([end, part, hands_on, e.name,
                      max(0.0, end - e.start_ns), e.start_ns])
    while stack:
        close(stack.pop())
    out["unlisted"] = dict(unlisted)
    return out


def launches(events) -> dict | None:
    """Every `llm.dispatch` interval of the loop thread's line that holds a
    jitted call, split:

        {"launches": [{"kind", "dispatch_ns", "wrapper_ns", "args_ns",
                       "put_ns", "execute_ns", "unlisted_ns",
                       "unlisted": {name: ns}}, ...],
         "without_call": dispatch intervals with no `PjitFunction` inside,
         "runtime_line": the line the runtime's events were taken from}

    `args_ns` holds `wrapper_ns`; the four parts sum to `dispatch_ns`."""
    events = list(events)
    line = span_gaps.step_line(events)
    if line is None:
        return None
    runtime = runtime_line(events, line)
    mine = sorted((e for e in events if (e.plane, e.line) in (line, runtime)),
                  key=lambda e: e.start_ns)
    starts = [e.start_ns for e in mine]
    found, without = [], 0
    for d in mine:
        if d.name != DISPATCH or (d.plane, d.line) != line:
            continue
        a, b = d.start_ns, d.start_ns + d.dur_ns
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        call = next((e for e in mine[lo:hi] if kind_of(e.name)
                     and (e.plane, e.line) == line), None)
        if call is None:
            without += 1
            continue
        c0, c1 = call.start_ns, min(b, call.start_ns + call.dur_ns)
        rec = _split([e for e in mine[lo:hi]
                      if c0 <= e.start_ns < c1 and e is not d], c1)
        rec["kind"] = kind_of(call.name)
        rec["dispatch_ns"] = b - a
        rec["wrapper_ns"] = (b - a) - (c1 - c0)
        rec["args_ns"] += rec["wrapper_ns"]
        found.append(rec)
    return {"launches": found, "without_call": without,
            "runtime_line": runtime[1] if runtime else None}


def _account(observed: dict) -> dict | None:
    """`launches` of the traced window, kept on `observed`: four readers
    and the printed line ask for it."""
    if not observed.get("events"):
        return None
    if "launch_account" not in observed:
        observed["launch_account"] = launches(observed["events"])
    return observed["launch_account"]


def mean_part_ms(observed: dict, kind: str, part: str) -> float | None:
    """Mean over the traced window's launches of `kind` of one part
    ("args", "put", "execute", "unlisted", "wrapper", "dispatch")."""
    found = _account(observed)
    mine = [r for r in (found or {}).get("launches", ())
            if r["kind"] == kind]
    if not mine:
        return None
    return sum(r[part + "_ns"] for r in mine) / len(mine) / 1e6


def _rose(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def window_launch(observed: dict, kind: str, since: dict | None = None,
                  until: dict | None = None) -> dict | None:
    """The window's rise of `engine_stats()["launch"][kind]`; `since` /
    `until`: another pair of stats than the window's two edges. None for
    a program without the counter, and over no call."""
    found = gap_account.window_stats(observed, "launch")
    if found is None:
        return None
    after = (until or {}).get("launch", found[0])
    before = (since or {}).get("launch", found[1])
    rose = _rose(after[kind], before[kind])
    return rose if rose["calls"] > 0 else None


def _a_call(rose: dict) -> dict:
    return {"launch_ms": 1e3 * rose["wall_s"] / rose["calls"],
            "calls": rose["calls"]}


def launch_ms(observed: dict, kind: str) -> float | None:
    """d`wall_s` / d`calls` of the jitted call alone, over the window."""
    rose = window_launch(observed, kind)
    return rose and _a_call(rose)["launch_ms"]


def by_tracing(observed: dict, kind: str) -> dict | None:
    """`launch_ms` with the profiler off and on, from the polls of the
    traced run: "untraced" from the window's start to the last poll before
    the profiler started, "traced" from that poll to the window's end (the
    trace's seconds and at most a poll's interval before them)."""
    from benchmark.kinds.serve import TRACE_FOR_S

    polls = observed.get("polls") or []
    if gap_account.window_stats(observed, "launch") is None or not polls:
        return None
    end = observed["after"]["stats"]["loop"]["wall_s"]
    quiet = [p for p in polls if "launch" in p
             and p["loop"]["wall_s"] < end - TRACE_FOR_S]
    if not quiet:
        return None
    out = {}
    for name, rose in (
            ("untraced", window_launch(observed, kind, until=quiet[-1])),
            ("traced", window_launch(observed, kind, since=quiet[-1]))):
        if rose:
            out[name] = _a_call(rose)
    return out or None


def report(observed: dict) -> dict | None:
    """The `[launch] {json}` line of a traced run, printed once: by kind of
    program the traced window's mean launch in its four parts with the
    wrapper apart, the five unlisted names with most time, and from the
    counters what a call hands the runtime (host arrays and bytes a call,
    the resident leaves), the launch with the profiler off and on, the
    fetch's three parts against its phase; the window's overlap account
    (`stats()["overlap"]`). None without a trace of the loop's events."""
    found = _account(observed)
    if found is None:
        return None
    if "launch_report" in observed:
        return observed["launch_report"]
    kinds = {}
    for kind in sorted({r["kind"] for r in found["launches"]}):
        mine = [r for r in found["launches"] if r["kind"] == kind]
        rec = {"launches": len(mine)}
        for part in ("dispatch", "wrapper", *PARTS):
            rec[part + "_ms"] = mean_part_ms(observed, kind, part)
        names: Counter = Counter()
        for r in mine:
            names.update(r["unlisted"])
        rec["unlisted_top_ms"] = [[n, ns / len(mine) / 1e6]
                                  for n, ns in names.most_common(5)]
        rose = window_launch(observed, kind)
        if rose:
            rec["counters"] = {
                **_a_call(rose),
                **{k: rose[k] / rose["calls"] for k in
                   ("host_arrays", "host_bytes")},
                **(by_tracing(observed, kind) or {})}
        kinds[kind] = rec
    out = {"kinds": kinds, "without_call": found["without_call"],
           "runtime_line": found["runtime_line"]}
    launch = gap_account.window_stats(observed, "launch")
    if launch is not None:
        out["resident_leaves"] = launch[0]["resident_leaves"]
    fetch = gap_account.window_stats(observed, "fetch")
    phases = gap_account.window_stats(observed, "step_phase_seconds")
    if fetch is not None and phases is not None:
        out["fetch"] = {kind: _rose(n, fetch[1][kind])
                        for kind, n in fetch[0].items()}
        phase = phases[0]["fetch"] - phases[1]["fetch"]
        parts = sum(v for n in out["fetch"].values() for v in n.values())
        out["fetch"]["phase_s"] = phase
        # the three parts of every kind over the phase they split: near 1
        out["fetch"]["parts_over_phase"] = parts / phase if phase else None
    overlap = gap_account.window_stats(observed, "overlap")
    if overlap is not None:
        out["overlap"] = {
            k: _rose(overlap[0][k], overlap[1][k])
            for k in ("launched_ahead", "launched_drained", "drains")}
    observed["launch_report"] = out
    print("[launch] " + json.dumps(out), flush=True)
    return out
