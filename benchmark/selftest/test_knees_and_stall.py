"""The open-loop cells' knees, the sweep's rule for one, the per-run stall
line and the watcher's window edges. CPU only, no cluster, no JAX.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_knees_and_stall.py -q -p no:cacheprovider
"""

import glob
import json
import math
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import readers, stall, sweep  # noqa: E402


def _json(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


OPEN_LOOPS = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json"))
    if _json(p).get("loop") == "open")


def test_there_are_open_loops():
    assert len(OPEN_LOOPS) >= 2, OPEN_LOOPS


@pytest.mark.parametrize("path", OPEN_LOOPS)
def test_rate_is_four_fifths_of_a_swept_knee(path):
    """`rate_rps` is 0.8 of `knee_rps`; `knee_sweep` holds the sweep's rows
    (five rates or more, two or more above the knee, the generator's
    lateness at each) and `sweep.knee` finds `knee_rps` in them;
    `knee_from` says it in words and names a rate above the knee. A file
    whose rate lies under 0.8 of its knee says why in `rate_why`."""
    t = _json(path)
    if "rate_why" in t:
        assert t["rate_rps"] < 0.8 * t["knee_rps"]
        assert len(t["rate_why"]) > 100 and "0.8" in t["rate_why"]
    else:
        assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=1e-9)
    rows = t["knee_sweep"]
    assert len({r["rate_rps"] for r in rows}) >= 5
    assert all("late_ms" in r for r in rows)
    found = sweep.knee(rows)
    assert found["knee_rps"] == t["knee_rps"], found
    assert len(found["above"]) >= 2, found
    named = [float(x) for x in re.findall(
        r"(?<![\d.])(\d+(?:\.\d+)?) req/s", t["knee_from"])]
    assert any(r > t["knee_rps"] for r in named), named
    assert "late" in t["knee_from"]


def _row(rate, mid, end):
    return {"rate_rps": rate, "backlog_mid": mid, "backlog_end": end}


@pytest.mark.parametrize("mid,end,trend", [
    (8.7, 5.9, "falling"), (8.4, 9.1, "level"), (0.4, 1.3, "level"),
    (15.8, 24.1, "growing"), (40.0, 51.0, "growing"), (3.0, 3.5, "level"),
    (100.0, 120.0, "level")])
def test_backlog_trend(mid, end, trend):
    assert sweep.backlog_trend(mid, end) == trend


@pytest.mark.parametrize("rows,knee_rps,above", [
    # level under the knee, growing above it, in any order
    ([_row(2.6, 15.8, 24.1), _row(1.0, 3.0, 2.9), _row(2.2, 8.4, 9.1),
      _row(3.0, 20.0, 41.0)], 2.2, [2.6, 3.0]),
    # falling under the knee
    ([_row(0.4, 8.7, 5.9), _row(0.6, 15.4, 19.8), _row(0.7, 20.0, 26.0)],
     0.4, [0.6, 0.7]),
    # a rate that does not grow above one that does is not the knee
    ([_row(1.0, 3.0, 3.0), _row(2.0, 9.0, 14.0), _row(3.0, 30.0, 31.0)],
     1.0, [2.0]),
    # the sweep never reached a growing backlog: no knee, and it says so
    ([_row(1.0, 3.0, 2.9), _row(2.0, 8.4, 5.1), _row(4.0, 9.0, 9.5)],
     None, []),
    # every rate grew: no knee either
    ([_row(1.0, 3.0, 12.9), _row(2.0, 8.4, 25.1)], None, [1.0, 2.0]),
    # two readings at one rate that disagree: the rate is past the knee
    ([_row(3.5, 31.0, 20.0), _row(4.0, 37.2, 32.7), _row(4.0, 20.9, 31.2),
      _row(4.25, 53.7, 30.4), _row(4.5, 46.4, 70.5)], 3.5, [4.0, 4.5]),
])
def test_knee_rule(rows, knee_rps, above):
    found = sweep.knee(rows)
    assert found["knee_rps"] == knee_rps
    assert found["above"] == above
    if knee_rps is None:
        assert "sweep" in found["why"]


# ------------------------------------------------------------ stall line

EDGES = [0.1 * i for i in range(1, 201)] + [
    20.0 * 2 ** i for i in range(1, 11)]


def _hist(values):
    counts = [0] * (len(EDGES) + 1)
    for v in values:
        counts[next((i for i, e in enumerate(EDGES) if v < e),
                    len(EDGES))] += 1
    return counts


def _stats(decode_gaps, chunk_gaps, turns_ms, over_s=0.0):
    zero = [0] * (len(EDGES) + 1)
    handed = decode_gaps + chunk_gaps
    return {
        "step_phase_seconds": {"fetch": sum(turns_ms) / 2e3,
                               "dispatch": sum(turns_ms) / 4e3},
        "compiled_programs": 12 + (over_s > 0),
        "stream": {"items": len(handed), "pickup_s": 4e-4 * len(handed),
                   "ship_s": 5e-4 * len(handed), "edges_ms": EDGES,
                   "handoff": _hist([0.95] * len(handed)), "max_ms": 0.95},
        "token_gaps": {
            "edges_ms": EDGES,
            "by_cause": {"after_preempt": zero, "after_drain": zero,
                         "after_prefill": _hist(chunk_gaps),
                         "decode": _hist(decode_gaps)},
            "sum_ms": {}, "burst": 0,
            "max_ms": {"after_preempt": 0.0, "after_drain": 0.0,
                       "after_prefill": max(chunk_gaps, default=0.0),
                       "decode": max(decode_gaps, default=0.0)}},
        "loop": {"wall_s": 1.0, "turns": {"decode": {
            "count": len(turns_ms), "wall_s": sum(turns_ms) / 1e3,
            "max_ms": max(turns_ms, default=0.0), "hist": _hist(turns_ms),
            "over_250ms_s": over_s}}}}


def test_stall_line_reads_the_windows_gaps_and_turns(capsys):
    before = _stats([4.05] * 10, [], [4.0] * 10)
    after = _stats([4.05] * 10 + [7.25] * 90 + [9.05] * 10,
                   [12.05] * 5, [4.0] * 10 + [7.0] * 100 + [300.5], 0.3)
    ticker = stall.Ticker(0.0, 0.0)
    ticker.max_ms, ticker.over_100ms = 31.0, 0
    out = stall.report(before, after, after, 0.0123, ticker)
    assert set(stall.FIELDS) <= set(out)
    assert out["gaps"]["decode"]["n"] == 100
    assert out["gaps"]["after_prefill"]["n"] == 5
    assert out["gaps"]["pooled"]["n"] == 105
    assert 7.2 <= out["gaps"]["decode"]["p50"] <= 7.3
    # 95% of 105 gaps: 99.75, so inside the 9.0-9.1 ms bucket
    assert 9.0 <= out["engine_itl_p95_ms"] <= 9.1
    assert out["gaps"]["after_prefill"]["max_ms"] == 12.05
    turn = out["turns"]["decode"]
    assert turn["max_ms"] == 300.5 and turn["over_250ms_s"] == 0.3
    assert turn["window_count"] == 101 and turn["window_top_ms"] == 320.0
    assert turn["window_over_250ms_s"] == pytest.approx(0.3)
    assert out["generator_late_ms"] == pytest.approx(12.3)
    assert out["handoff"]["items"] == 105
    assert out["handoff"]["pickup_mean_ms"] == pytest.approx(0.4)
    assert out["handoff"]["ship_mean_ms"] == pytest.approx(0.5)
    assert 0.9 <= out["handoff"]["p95_ms"] <= 1.0
    assert out["ticker_max_ms"] == 31.0
    # the window's turns (700 + 300.5 ms), half under `fetch`; one program
    # compiled inside it
    assert out["phase_s"]["fetch"] == pytest.approx(0.50025)
    assert out["phase_s"]["dispatch"] == pytest.approx(0.250125)
    assert out["compiled"] == 1
    printed = capsys.readouterr().out
    assert stall.parse(printed) == json.loads(json.dumps(out))
    assert "[gaps-hist]" not in printed  # the traced run's alone
    stall.report(before, after, after, 0.0123, ticker, hist=True)
    printed = capsys.readouterr().out
    assert "[gaps-hist] decode" in printed and " 7:90" in printed
    assert stall.histogram(before, after)["after_prefill"] == [(12.0, 5)]


def test_stall_line_counts_the_pages_held():
    def snap(used):
        return {**_stats([4.05], [], [4.0]), "kv": {"full": {
            "pages_used": used, "pages_total": 2111}}}

    out = stall.line(snap(40), snap(55), snap(0), 0.0, None,
                     polls=[snap(90), snap(70)])
    assert out["kv_pages"] == {"full": {"total": 2111, "first": 40,
                                        "last": 55, "most": 90}}
    assert stall.kv_pages([{"loop": {}}]) is None


def test_stall_line_without_the_windows_edges():
    final = _stats([4.0], [], [4.0, 5.0])
    out = stall.line(None, None, final, 0.0, None)
    assert set(stall.FIELDS) <= set(out)
    assert out["gaps"] is None and out["engine_itl_p95_ms"] is None
    assert out["turns"]["decode"]["max_ms"] == 5.0
    assert stall.parse("nothing here") is None


def test_ticker_hears_a_silence():
    t0 = time.monotonic()
    ticker = stall.Ticker(t0, t0 + 0.3).start()
    ticker.join()
    assert ticker.ticks >= 5
    assert 15.0 <= ticker.max_ms < 250.0


# ------------------------------------------------- the watcher's window

def test_after_snapshot_is_the_windows_end(monkeypatch):
    """A counter bumped after the window's end, while `trace_stop` is still
    at work, does not show in a delta reader: "after" is taken when the
    window ends, not when `trace_stop` returns."""
    from benchmark.kinds import serve as serve_kind
    from ray_tpu.util import state

    edge = {}

    def fake_call(method, *args, timeout=120.0):
        if method == "trace_start":
            edge["traced_at"] = time.monotonic()
        if method == "engine_stats":
            late = time.monotonic() > edge["end"] + 0.25
            return {"preemptions": 7 if late else 3,
                    "finished_requests": 50 if late else 40}
        if method == "trace_stop":
            edge["stopped_at"] = time.monotonic()
            time.sleep(1.2)  # runs on past the window's end
        return True

    monkeypatch.setattr(serve_kind, "replica_call", fake_call)
    monkeypatch.setattr(state, "cluster_metrics", lambda: "")
    monkeypatch.setattr(serve_kind, "TRACE_FOR_S", 0.3)
    monkeypatch.setattr(serve_kind, "POLL_S", 0.1)
    t0, seconds = time.monotonic() + 0.1, 1.0
    edge["end"] = t0 + seconds
    observed = {}
    serve_kind._watch(t0, seconds, "/nowhere", observed)
    assert time.monotonic() > edge["end"] + 0.25  # trace_stop was waited for
    assert observed["trace_stop_s"] >= 1.2
    assert observed["after_late_s"] < 0.2
    assert readers.counter_delta(observed, "preemptions") == 0
    assert readers.counter_delta(observed, "finished_requests") == 0
    assert observed["polls"] and all(p["preemptions"] == 3
                                     for p in observed["polls"])
    # the trace is the window's last TRACE_FOR_S seconds, and `trace_stop`
    # is not issued before "after" is taken
    assert edge["traced_at"] == pytest.approx(t0 + 0.7, abs=0.1)
    assert edge["stopped_at"] >= edge["end"]
    # the untraced run's watcher: the two edges and nothing else
    observed = {}
    t0 = time.monotonic() + 0.05
    edge["end"] = t0 + 0.3
    serve_kind._watch(t0, 0.3, None, observed)
    assert set(observed) == {"before", "after", "after_late_s"}
    assert set(observed["after"]) == {"stats"}


# ------------------------------------------------ the deployment's pool

BENCH = _json("BENCHMARK.json")
GPT2_LARGE_CELLS = [w for w in BENCH["workloads"]
                    if w["config"] == "gpt2-large"]


def test_gpt2_large_has_three_cells():
    assert len(GPT2_LARGE_CELLS) == 3


@pytest.mark.parametrize("cell", GPT2_LARGE_CELLS,
                         ids=[w["traffic"] for w in GPT2_LARGE_CELLS])
def test_pool_holds_every_lane_at_the_longest_request(cell):
    """`num_blocks` pages hold `max_batch_size` lanes, each at the longest
    request its traffic file can send, beside the null page."""
    engine = _json("benchmark/configs/gpt2-large.json")["engine"]
    traffic = _json(f"benchmark/traffic/{cell['traffic']}.json")
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= engine["max_model_len"]
    pages = math.ceil(longest / engine["block_size"])
    assert engine["max_batch_size"] * pages + 1 <= engine["num_blocks"]
    deployment = _json("benchmark/configs/gpt2-large.json")["deployment"]
    assert deployment["max_ongoing_requests"] >= engine["max_batch_size"]
    if traffic["loop"] == "closed":
        assert traffic["clients"] <= deployment["max_ongoing_requests"]
