"""CPU tests of `sched_decode_lanes_pct` and `sched_multi_chunk_rounds_pct`
(PR 50): the two readers on a made-up `observed` (the window's delta of the
scheduler's counters, which `engine_stats()` carries at its top level), on a
parent's stats, which lack the counters, and on a window with no decode
step; and the two names against `BENCHMARK.json`. Run by hand with the rest
of `benchmark/selftest`."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("sched_decode_lanes_pct", "sched_multi_chunk_rounds_pct")


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def stats(steps, lanes, chunks, multi, rows=64):
    """An engine's stats with the scheduler's four counters among the
    keys `depth()` always had."""
    return {"waiting": 64, "running": rows, "preemptions": 0,
            "max_batch_size": rows, "decode_steps": steps,
            "decode_lanes": lanes, "continuation_chunks": chunks,
            "multi_chunk_rounds": multi,
            # a stateful family's own count, by the program's rows: not
            # what these readers take
            "state": {"decode_steps": {"64": steps}, "decode_lanes": 1}}


def window(before, after):
    return {"before": {"stats": before}, "after": {"stats": after}}


def test_the_readers_take_the_windows_delta():
    # 1,400 decode steps of 61.5 lanes in the window, 3,500 continuation
    # chunks, 1,000 of the steps behind more than one; warm-up and the
    # pre-roll (300 steps of 20 lanes) lie before it and do not count
    obs = window(stats(300, 6_000, 200, 10),
                 stats(1_700, 6_000 + 86_100, 3_700, 1_010))
    assert reader(NEW[0])(obs) == pytest.approx(100 * 61.5 / 64)
    assert reader(NEW[1])(obs) == pytest.approx(100 * 1_000 / 1_400)
    # the share is of the engine's lanes, not of the program's rows
    obs = window(stats(0, 0, 0, 0, rows=32), stats(100, 1_280, 100, 0,
                                                   rows=32))
    assert reader(NEW[0])(obs) == pytest.approx(40.0)
    assert reader(NEW[1])(obs) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    read = reader(name)
    assert read({}) is None
    assert read({"before": None, "after": None}) is None
    # the parent: `depth()` without the counters, a stateful family's own
    # `decode_steps` under "state" notwithstanding
    old = {"waiting": 0, "running": 3, "preemptions": 0,
           "max_batch_size": 64,
           "state": {"decode_steps": {"32": 9}, "decode_lanes": 200}}
    assert read(window(old, old)) is None
    # a window with no decode step (chunks alone): nothing to divide by
    still = stats(300, 6_000, 200, 10)
    assert read(window(still, stats(300, 6_000, 900, 10))) is None


def test_the_new_names_resolve_and_obey_the_rules():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    lanes, rounds = (by_name[n] for n in NEW)
    assert (lanes["moves"], lanes["better"]) == ("serve_tokens_per_s",
                                                 "higher")
    assert (rounds["moves"], rounds["better"]) == ("itl_p95_ms", "lower")
    assert lanes["workloads"] == cells["serve_tokens_per_s"]
    assert rounds["workloads"] == cells["itl_p95_ms"]
    for m in (lanes, rounds):
        assert (m["unit"], m["source"], m["layer"]) == (
            "%", "program_counter", "engine scheduler")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
