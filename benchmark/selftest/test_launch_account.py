"""CPU tests of `benchmark/launch_account.py` and the seven readers built on
it, on a hand-worked event list, hand-worked counters and three launches cut
from a chip trace (`launch_excerpt.json`). Collected into
tier-1 by `tests/test_benchmark_selftests.py`; by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_launch_account.py -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import launch_account, run  # noqa: E402
from benchmark.trace_reduce import Event  # noqa: E402

HOST, LOOP, RUNTIME, WORKER = "/host:CPU", "python3", "", "pjrt-tpu-tasks/7"


def host(name, a, b, line=LOOP):
    """Times in microseconds."""
    return Event(HOST, line, name, a * 1e3, (b - a) * 1e3)


def events():
    """Two turns of the loop. The first reads a PREFILL step and launches a
    decode program behind it (the launch's kind is the program's); the
    second reads that decode step and launches a chunk.

    decode launch, llm.dispatch [100, 1100]:
      PjitFunction(_decode_impl) [110, 1090], nested once more [112, 1088]
        ParseArguments [120, 170]                         args, by ARGS
        DevicePut [200, 400]                              put
          (runtime) AllocateRawBuffer [210, 280]            under DevicePut
            (runtime) DeferredTpuAllocator::Allocate [215, 275]  two deep
          (runtime) TpuClient::LinearizeIntoImpl [290, 390]
        DevicePut [400, 500]
        SomethingNew [500, 530]                           unlisted
          (runtime) InsideSomethingNew [505, 515]           unlisted too
        PJRT_LoadedExecutable_Execute linkage [600, 601]  unlisted
        (runtime) PJRT_LoadedExecutable_Execute [602, 1000]   execute
          (runtime) AllocateOutputBuffersWithInputReuse [650, 850]
            (runtime) AllocateRawBuffer [660, 700]    the execute's, not put
      (worker) Linearize [300, 700]       another thread: not the launch's
    chunk launch, llm.dispatch [2000, 2500], cut by the window's end:
      PjitFunction(_chunk_impl) [2010, 2600]  straddles the interval's end
        DevicePut [2100, 2700]                straddles both
    a dispatch that raised before its call, llm.dispatch [3000, 3010]
    """
    return [
        host("llm.step.prefill", 90, 1900),
        host("llm.prepare", 92, 99),
        host("llm.dispatch", 100, 1100),
        host("PjitFunction(_decode_impl)", 110, 1090),
        host("PjitFunction(_decode_impl)", 112, 1088),
        host("ParseArguments", 120, 170),
        host("DevicePut", 200, 400),
        host("AllocateRawBuffer", 210, 280, RUNTIME),
        host("DeferredTpuAllocator::Allocate", 215, 275, RUNTIME),
        host("TpuClient::LinearizeIntoImpl", 290, 390, RUNTIME),
        host("DevicePut", 400, 500),
        host("SomethingNew", 500, 530),
        host("InsideSomethingNew", 505, 515, RUNTIME),
        host("PJRT_LoadedExecutable_Execute linkage", 600, 601),
        host("PJRT_LoadedExecutable_Execute", 602, 1000, RUNTIME),
        host("AllocateOutputBuffersWithInputReuse", 650, 850, RUNTIME),
        host("AllocateRawBuffer", 660, 700, RUNTIME),
        host("Linearize", 300, 700, WORKER),
        host("llm.fetch", 1100, 1800),
        host("np.asarray(jax.Array)", 1101, 1700),
        host("llm.step.decode", 1950, 2950),
        host("llm.dispatch", 2000, 2500),
        host("PjitFunction(_chunk_impl)", 2010, 2600),
        host("DevicePut", 2100, 2700),
        host("llm.step.decode", 2990, 3100),
        host("llm.dispatch", 3000, 3010),
        # the worker's own execute events are on no PjitFunction of the
        # loop's: its line is not taken for the runtime's
        host("PJRT_LoadedExecutable_Execute", 5000, 5100, WORKER),
        # another thread's spans are not the step loop's
        host("llm.request", 0, 6000, line="control/9"),
    ]


def test_split_by_hand():
    found = launch_account.launches(events())
    assert found["runtime_line"] == RUNTIME
    assert found["without_call"] == 1
    decode, chunk = found["launches"]
    # the launch is the PROGRAM's kind, not the turn's it lies in
    assert (decode["kind"], chunk["kind"]) == ("decode", "prefill")
    us = 1e3
    assert decode["dispatch_ns"] == pytest.approx(1000 * us)
    assert decode["wrapper_ns"] == pytest.approx(20 * us)
    # put: both DevicePuts whole (their children are theirs, two deep)
    assert decode["put_ns"] == pytest.approx(300 * us)
    # execute: the whole PJRT call; its output buffers' AllocateRawBuffer
    # is not a transfer
    assert decode["execute_ns"] == pytest.approx(398 * us)
    # unlisted: SomethingNew and its child, the linkage event
    assert decode["unlisted_ns"] == pytest.approx(31 * us)
    assert decode["unlisted"] == pytest.approx({
        "SomethingNew": 20 * us, "InsideSomethingNew": 10 * us,
        "PJRT_LoadedExecutable_Execute linkage": 1 * us})
    # args: the wrapper 20, the outer call's self time 4, the inner's
    # (976 less its children 50 + 300 + 30 + 1 + 398 = 779: 197), and
    # ParseArguments 50
    assert decode["args_ns"] == pytest.approx((20 + 4 + 197 + 50) * us)
    for rec in (decode, chunk):
        assert sum(rec[p + "_ns"] for p in launch_account.PARTS) \
            == pytest.approx(rec["dispatch_ns"])
    # the call and its child straddle the interval's end and are cut there
    assert chunk["dispatch_ns"] == pytest.approx(500 * us)
    assert chunk["wrapper_ns"] == pytest.approx(10 * us)
    assert chunk["put_ns"] == pytest.approx(400 * us)
    assert chunk["args_ns"] == pytest.approx(100 * us)
    assert chunk["execute_ns"] == chunk["unlisted_ns"] == 0


def test_without_the_runtimes_line_its_time_is_the_calls_own():
    alone = [e for e in events() if e.line != RUNTIME]
    found = launch_account.launches(alone)
    assert found["runtime_line"] is None
    decode = found["launches"][0]
    assert decode["execute_ns"] == 0
    assert decode["put_ns"] == pytest.approx(300e3)
    assert sum(decode[p + "_ns"] for p in launch_account.PARTS) \
        == pytest.approx(decode["dispatch_ns"])


def excerpt():
    """Three turns of chat-steady's loop as a v5e's trace holds them."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "launch_excerpt.json")) as f:
        cut = json.load(f)
    return [Event(cut["plane"], cut["lines"][line], cut["names"][name],
                  float(start), float(dur))
            for line, name, start, dur in cut["events"]]


def test_a_chips_own_launches():
    """The names as the runtime prints them: three decode launches of 1.7
    to 1.8 ms, the runtime's events on the line named "", a launch's
    largest part the transfers of its eight host arrays, and next to
    nothing under no list."""
    found = launch_account.launches(excerpt())
    assert found["runtime_line"] == "" and found["without_call"] == 0
    assert [r["kind"] for r in found["launches"]] == ["decode"] * 3
    for rec in found["launches"]:
        parts = {p: rec[p + "_ns"] for p in launch_account.PARTS}
        assert sum(parts.values()) == pytest.approx(rec["dispatch_ns"])
        assert 1.6e6 < rec["dispatch_ns"] < 1.9e6
        assert max(parts, key=parts.get) == "put"
        assert 1.0e6 < parts["put"] < 1.25e6
        assert 0.35e6 < parts["execute"] < 0.5e6
        assert 0.15e6 < parts["args"] < 0.25e6
        assert 0.05e6 < rec["wrapper_ns"] < 0.1e6
        assert parts["unlisted"] < 0.01 * rec["dispatch_ns"]
        assert set(rec["unlisted"]) == {
            "PythonRefManager::CollectGarbage", "Wait for donation holds",
            "PJRT_LoadedExecutable_Execute linkage", "Wait for usage holds"}
    # eight host arrays a decode launch, each its own DevicePut
    loop = [e for e in excerpt() if e.line == LOOP]
    assert sum(e.name == "DevicePut" for e in loop) == 8 * 3
    # without the runtime's line the transfers are still DevicePut's, and
    # the execute shows as the call's own time
    alone = launch_account.launches(
        [e for e in excerpt() if e.line != RUNTIME])
    assert alone["runtime_line"] is None
    for rec, whole in zip(alone["launches"], found["launches"]):
        assert rec["execute_ns"] == 0
        assert rec["put_ns"] == pytest.approx(whole["put_ns"])
        assert rec["args_ns"] == pytest.approx(
            whole["args_ns"] + whole["execute_ns"] + whole["unlisted_ns"]
            - rec["unlisted_ns"])


def _stats(calls, wall, copy, steps, loop_s, ahead=0):
    launch = {kind: {"calls": 0, "wall_s": 0.0, "host_arrays": 0,
                     "host_bytes": 0}
              for kind in ("decode", "prefill", "verify")}
    launch["decode"] = {"calls": calls, "wall_s": wall,
                        "host_arrays": 8 * calls, "host_bytes": 900 * calls}
    launch["resident_leaves"] = 440
    fetch = {kind: {"wait_s": 0.0, "copy_s": 0.0, "order_s": 0.0}
             for kind in ("decode", "prefill", "verify")}
    fetch["decode"] = {"wait_s": 2.0 * copy, "copy_s": copy,
                       "order_s": 0.1 * copy}
    return {"launch": launch, "fetch": fetch,
            "steps": {"decode": steps, "prefill": 0},
            "step_phase_seconds": {"fetch": 3.1 * copy, "dispatch": wall},
            "loop": {"wall_s": loop_s},
            "overlap": {"launched_ahead": {"decode": ahead, "prefill": 0},
                        "launched_drained": {"decode": 3, "prefill": 1},
                        "drains": {"idle": 2 + ahead, "swap": 0},
                        "discarded_tokens": 0}}


def _observed():
    """A window of 51 s whose last 2 s are traced: 1.0 ms a launch with
    the profiler off, 3.0 ms with it on."""
    return {"before": {"stats": _stats(100, 0.1, 0.01, 100, 10.0)},
            "polls": [_stats(5000, 5.0, 0.5, 5000, 35.0, ahead=4800),
                      _stats(9600, 9.6, 0.96, 9600, 58.6, ahead=9400),
                      # the profiler started between these two
                      _stats(9800, 10.2, 0.98, 9800, 59.6, ahead=9600)],
            "after": {"stats": _stats(10100, 11.1, 1.01, 10100, 61.0,
                                      ahead=9900)},
            "events": events()}


def test_readers_by_hand(capsys):
    observed = _observed()
    read = run.read_layer_metric
    assert read("decode_launch_ms", observed) == pytest.approx(1.1)
    # the copy and the rows put back in order, a step: (1.0 + 0.1) / 10^4
    assert read("decode_fetch_copy_ms", observed) == pytest.approx(0.11)
    assert read("decode_launch_args_ms", observed) == pytest.approx(0.271)
    assert read("decode_launch_put_ms", observed) == pytest.approx(0.3)
    assert read("prefill_launch_args_ms", observed) == pytest.approx(0.1)
    assert read("prefill_launch_put_ms", observed) == pytest.approx(0.4)
    # no prompt's or chunk's program ran by the counters
    assert read("prefill_launch_ms", observed) is None
    # the line is printed once, by whichever reader comes first
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[launch] ")]
    assert len(lines) == 1
    line = json.loads(lines[0][len("[launch] "):])
    decode = line["kinds"]["decode"]
    assert decode["launches"] == 1
    assert decode["dispatch_ms"] == pytest.approx(
        sum(decode[p + "_ms"] for p in launch_account.PARTS))
    assert decode["wrapper_ms"] == pytest.approx(0.02)
    assert decode["unlisted_top_ms"][0] == ["SomethingNew",
                                            pytest.approx(0.02)]
    counters = decode["counters"]
    assert (counters["host_arrays"], counters["host_bytes"]) == (8, 900)
    assert line["resident_leaves"] == 440
    assert counters["untraced"]["launch_ms"] == pytest.approx(1.0)
    assert counters["untraced"]["calls"] == 9500
    assert counters["traced"]["launch_ms"] == pytest.approx(3.0)
    assert "counters" not in line["kinds"]["prefill"]
    assert line["without_call"] == 1 and line["runtime_line"] == RUNTIME
    assert line["fetch"]["decode"] == pytest.approx(
        {"wait_s": 2.0, "copy_s": 1.0, "order_s": 0.1})
    assert line["fetch"]["phase_s"] == pytest.approx(3.1)
    assert line["fetch"]["parts_over_phase"] == pytest.approx(1.0)
    assert line["overlap"] == {
        "launched_ahead": {"decode": 9900, "prefill": 0},
        "launched_drained": {"decode": 0, "prefill": 0},
        "drains": {"idle": 9900, "swap": 0}}


NAMES = [f"{kind}_launch_{what}" for kind in ("decode", "prefill")
         for what in ("ms", "args_ms", "put_ms")] \
    + ["decode_fetch_copy_ms"]


def test_the_parents_program_and_a_bare_trace_read_nothing():
    """The parent commit's `engine_stats()` has neither `launch` nor
    `fetch`: the counters' readers give None and the spans' still read the
    trace, which the parent writes too. No `llm.step.*`: None everywhere."""
    old = {"steps": {"decode": 5, "prefill": 0},
           "overlap": _stats(1, 1, 1, 1, 1)["overlap"],
           "step_phase_seconds": {"fetch": 1.0}, "loop": {"wall_s": 1.0}}
    parent = {"before": {"stats": old}, "after": {"stats": old},
              "polls": [old], "events": events()}
    read = run.read_layer_metric
    for name in NAMES:
        spans = name.endswith(("args_ms", "put_ms"))
        assert (read(name, parent) is not None) == spans, name
    assert "counters" not in \
        launch_account.report(parent)["kinds"]["decode"]
    bare = [e for e in events() if not e.name.startswith("llm.step.")]
    assert launch_account.launches(bare) is None
    for events_ in (bare, None):
        observed = {**_observed(), "events": events_}
        assert launch_account.report(observed) is None
        for name in NAMES:
            if name.endswith(("args_ms", "put_ms")):
                assert read(name, observed) is None, name


def test_every_metric_is_declared_for_the_cells_that_read_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NAMES}
    assert sorted(mine) == sorted(NAMES)
    assert [m["name"] for m in bench["per_layer"]][-len(NAMES):] == NAMES
    for name, m in mine.items():
        assert m["layer"] == "runner dispatch"
        decode = name.startswith("decode")
        assert m["moves"] == ("itl_p95_ms" if decode
                              else "serve_tokens_per_s")
        assert m["workloads"] == (
            ["serve-gpt2-large-chat-steady", "serve-gpt2-large-long-decode"]
            if decode else ["serve-gpt2-large-prefill-sat",
                            "serve-lfm2-8b-a1b-rag-agent-sat"])
        assert m["better"] == "lower" and m["unit"] == "ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
