"""CPU test of `ctx_kernel_steps_pct` (PR 41): the reader on synthetic
polls of `engine_stats()`, and on a parent's polls, which lack the
counter. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(observed):
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "ctx_kernel_steps_pct.py")
    spec = importlib.util.spec_from_file_location("m_ctx_kernel", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(observed)


def _poll(steps, **kinds):
    return {"steps": {"decode": steps, "prefill": 7},
            "context_by_kind": {
                name: {"decode": {"slots_read": 10, "kernel_steps": n},
                       "prefill": {"slots_read": 10, "kernel_steps": 0},
                       "verify": {"slots_read": 0, "kernel_steps": 0}}
                for name, n in kinds.items()}}


def test_the_share_is_of_the_windows_decode_steps():
    polls = [_poll(100, full=101), _poll(300, full=300),
             _poll(1100, full=1101)]
    assert _read({"polls": polls}) == pytest.approx(100.0)
    # warm-up and the pre-roll lie before the first poll and do not count
    assert _read({"polls": polls[1:]}) == pytest.approx(100 * 801 / 800)
    # a family whose other kind keeps the loops: a launch is one launch
    two = [_poll(100, full=100, window=0), _poll(200, full=200, window=0)]
    assert _read({"polls": two}) == pytest.approx(100.0)
    loops = [_poll(100, full=0), _poll(200, full=0)]
    assert _read({"polls": loops}) == 0.0


def test_nothing_to_read_gives_none():
    parent = {"steps": {"decode": 5}, "context_by_kind": {
        "full": {"decode": {"slots_read": 10}}}}
    later = {"steps": {"decode": 9}, "context_by_kind": {
        "full": {"decode": {"slots_read": 30}}}}
    assert _read({"polls": [parent, later]}) is None
    assert _read({"polls": []}) is None
    assert _read({}) is None
    assert _read({"polls": [_poll(5, full=5)]}) is None
    still = _poll(5, full=5)
    assert _read({"polls": [still, still]}) is None
    assert _read({"polls": [{"steps": {"decode": 1}}, {"steps": {
        "decode": 2}}]}) is None
