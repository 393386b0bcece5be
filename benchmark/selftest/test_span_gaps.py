"""CPU tests of `benchmark/span_gaps.py` and the readers built on it, on a
hand-worked event list. Run by hand, like the other self-tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest -q -p no:cacheprovider
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, span_gaps, trace_reduce  # noqa: E402
from benchmark.trace_reduce import Event  # noqa: E402

DEV, OPS = "/device:TPU:0", trace_reduce.OPS_LINE
HOST, LOOP = "/host:CPU", "llm-engine-loop/7"


def events(device_shift: float = 0.0):
    """Times in units of 10 us (a program gap is well over
    `span_gaps.PROGRAM_GAP_NS`). Three whole steps and a fourth the window cut (its
    device operations are there, its annotation is not):

    device busy   [125,370]      [435,750]          [930,1280]  [1320,1500]
    step 0 decode [100 ........ 400]
    step 1 decode                [410 ........ 800]     (interval 400-800)
    llm.idle                                    [800,900]
    step 2 prefill                                [905 ..... 1300] (800-1300)
    """
    def host(name, a, b, line=LOOP):
        return Event(HOST, line, name, a * 1e4, (b - a) * 1e4)

    def dev(a, b):
        return Event(DEV, OPS, "fusion.1", (a + device_shift) * 1e4,
                     (b - a) * 1e4)

    return [
        dev(125, 370), dev(435, 750), dev(930, 1280), dev(1320, 1500),
        Event(DEV, "XLA Modules", "jit__decode_impl", 125e4, 245e4),
        host("llm.step.decode", 100, 400),
        host("llm.dispatch", 110, 130), host("llm.fetch", 130, 380),
        host("llm.schedule", 400, 410),
        host("llm.step.decode", 410, 800),
        host("llm.prepare", 410, 420), host("llm.dispatch", 420, 440),
        host("PjitFunction(_decode_impl)", 421, 439),
        host("llm.fetch", 440, 760),
        host("np.asarray(jax.Array)", 441, 759),
        host("llm.commit", 760, 770), host("llm.emit", 770, 780),
        host("llm.bookkeep", 780, 800),
        host("llm.idle", 800, 900),
        host("llm.schedule", 900, 905),
        host("llm.step.prefill", 905, 1300),
        host("llm.prepare", 905, 915), host("llm.dispatch", 915, 935),
        host("llm.fetch", 935, 1290), host("llm.bookkeep", 1290, 1300),
        # another thread's spans are not the step loop's
        host("llm.request", 0, 1500, line="control/9"),
    ]


def test_split_by_hand():
    found = span_gaps.split(events())
    assert span_gaps.step_line(events()) == (HOST, LOOP)
    # the first step seen has no interval of its own: two whole steps
    one, two = found["steps"]
    assert (one["kind"], one["start_ns"], one["end_ns"]) == \
        ("decode", 400e4, 800e4)
    # step 1: idle [400,435] = schedule 10 + prepare 10 (host) and 15 of
    # dispatch; idle [750,800] = 10 of fetch and commit, emit, bookkeep 40
    assert one["fetch_ns"] == pytest.approx(10e4)
    assert one["dispatch_ns"] == pytest.approx(15e4)
    assert one["host_ns"] == pytest.approx(60e4)
    assert one["idle_ns"] == 0
    # step 2: idle [800,930] = 100 under llm.idle, schedule 5 + prepare 10,
    # 15 of dispatch; idle [1280,1300] = 10 of fetch + bookkeep 10
    assert (two["kind"], two["start_ns"], two["end_ns"]) == \
        ("prefill", 800e4, 1300e4)
    assert two["idle_ns"] == pytest.approx(100e4)
    assert two["fetch_ns"] == pytest.approx(10e4)
    assert two["dispatch_ns"] == pytest.approx(15e4)
    assert two["host_ns"] == pytest.approx(25e4)
    # cut at the edges: [370,400] before the first interval, [1300,1320]
    # after the last
    assert found["edges_ns"] == pytest.approx(50e4)
    assert found["shift_ns"] == 0
    # every idle instant is accounted for, and it is what reduce() reports
    assert found["total_ns"] == pytest.approx(285e4)
    inside = sum(r[k] for r in found["steps"]
                 for k in ("fetch_ns", "dispatch_ns", "host_ns", "idle_ns"))
    assert inside + found["edges_ns"] == pytest.approx(found["total_ns"])
    r = trace_reduce.reduce(events())
    assert (r["window_s"] - r["busy_s"]) * 1e9 == \
        pytest.approx(found["total_ns"])


def test_readers_by_hand():
    observed = {"events": events()}
    read = run.read_layer_metric
    assert read("decode_gap_ms", observed) == pytest.approx(0.85)
    assert read("decode_gap_fetch_ms", observed) == pytest.approx(0.10)
    assert read("decode_gap_host_ms", observed) == pytest.approx(0.60)
    # llm.idle is no part of a step's gap
    assert read("prefill_gap_ms", observed) == pytest.approx(0.50)
    assert read("prefill_gap_fetch_ms", observed) == pytest.approx(0.10)
    assert read("prefill_gap_host_ms", observed) == pytest.approx(0.25)
    for kind in ("decode", "prefill"):
        assert read(f"{kind}_gap_fetch_ms", observed) \
            + read(f"{kind}_gap_host_ms", observed) \
            <= read(f"{kind}_gap_ms", observed)


def test_a_trace_without_annotations_reads_nothing():
    """The parent commit's program: jax's own host events, no llm.*."""
    bare = [e for e in events() if not e.name.startswith("llm.")]
    assert span_gaps.split(bare) is None
    assert span_gaps.clock_check(bare) is None
    for name in ("decode_gap_ms", "decode_gap_fetch_ms",
                 "decode_gap_host_ms", "prefill_gap_ms",
                 "prefill_gap_fetch_ms", "prefill_gap_host_ms"):
        assert run.read_layer_metric(name, {"events": bare}) is None
        assert run.read_layer_metric(name, {"events": None}) is None


def test_one_clock():
    # every program starts 150 us after its dispatch began and ends 100 us
    # before its fetch returned: causal, nothing to move
    assert span_gaps.clock_check(events()) == {
        "programs": 3, "starts_early_ns": -15e4, "ends_late_ns": -10e4,
        "shift_ns": 0.0}
    # a device clock 300 us late: the programs end 200 us after their
    # fetches, and 200 us earlier is the least that mends it
    late = span_gaps.clock_check(events(device_shift=30.0))
    assert late["ends_late_ns"] == pytest.approx(20e4)
    assert late["shift_ns"] == pytest.approx(-20e4)
    # a device clock 400 us early: the programs start 250 us before their
    # dispatches
    early = span_gaps.clock_check(events(device_shift=-40.0))
    assert early["starts_early_ns"] == pytest.approx(25e4)
    assert early["shift_ns"] == pytest.approx(25e4)
    # split() works on the mended timeline: the whole gap and the host's
    # part of it are what they were, fetch and dispatch trade the rest
    straight, moved = span_gaps.split(events()), \
        span_gaps.split(events(device_shift=-40.0))
    assert moved["shift_ns"] == pytest.approx(25e4)
    assert moved["total_ns"] == pytest.approx(straight["total_ns"])
    for a, b in zip(straight["steps"], moved["steps"]):
        assert b["host_ns"] == pytest.approx(a["host_ns"])
        assert b["fetch_ns"] == pytest.approx(a["fetch_ns"] + 15e4)
        assert b["dispatch_ns"] == pytest.approx(a["dispatch_ns"] - 15e4)


def test_counter_and_startup_readers():
    def stats(steps, fetched):
        return {"stats": {
            "steps": {"decode": steps, "prefill": 3},
            "d2h_bytes": {"decode": fetched, "prefill": 999},
            "startup_seconds": {"init_params": 4.0, "build_runner": 1.5,
                                "warmup": 20.0, "warmup_trace": 6.0,
                                "warmup_lower": 5.0, "warmup_compile": 8.0},
            "warmup_cache": {"hits": 14, "misses": 2}}, "page": ""}

    observed = {"before": stats(10, 1_000_000), "ready_s": 33.0,
                "after": stats(60, 1_000_000 + 50 * 1_609_760)}
    read = run.read_layer_metric
    assert read("decode_d2h_kb_per_step", observed) == \
        pytest.approx(1609.76)
    assert read("replica_process_s", observed) == pytest.approx(7.5)
    assert read("replica_init_s", observed) == pytest.approx(5.5)
    assert read("warmup_trace_lower_s", observed) == pytest.approx(11.0)
    assert read("warmup_compile_s", observed) == pytest.approx(8.0)
    assert read("warmup_cache_misses", observed) == 2
    # replica_process_s + replica_init_s + warmup == replica_ready_s
    assert read("replica_process_s", observed) \
        + read("replica_init_s", observed) + 20.0 == \
        pytest.approx(read("replica_ready_s", observed))
    # the parent's engine_stats() has none of these keys
    old = {"before": {"stats": {"preemptions": 0}, "page": ""},
           "after": {"stats": {"preemptions": 0}, "page": ""},
           "ready_s": 33.0}
    for name in ("decode_d2h_kb_per_step", "replica_process_s",
                 "replica_init_s", "warmup_trace_lower_s",
                 "warmup_compile_s", "warmup_cache_misses"):
        assert read(name, old) is None
