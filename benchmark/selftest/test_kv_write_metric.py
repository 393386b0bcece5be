"""CPU test of `kv_write_paged_pct` (PR 37): the reader on synthetic polls
of `engine_stats()["kv"]`, and on a parent's polls, which lack the
counters. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(observed):
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "kv_write_paged_pct.py")
    spec = importlib.util.spec_from_file_location("m_kv_write", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(observed)


def _poll(**kinds):
    return {"kv": {name: {"pages_used": 3, "window": None,
                          "rows_written_paged": paged,
                          "rows_written_rowwise": rowwise}
                   for name, (paged, rowwise) in kinds.items()}}


def test_the_share_is_of_the_windows_rows_every_kind_together():
    polls = [_poll(full=(1000, 50), window=(1000, 50)),
             _poll(full=(1500, 60), window=(1500, 60)),
             _poll(full=(1900, 150), window=(1900, 150))]
    assert _read({"polls": polls}) == pytest.approx(100 * 900 / 1000)
    # warm-up and the pre-roll lie before the first poll and do not count
    assert _read({"polls": polls[1:]}) == pytest.approx(100 * 400 / 490)


def test_nothing_to_read_gives_none():
    parent = {"kv": {"full": {"pages_used": 3, "window": None}}}
    assert _read({"polls": [parent, parent]}) is None
    assert _read({"polls": []}) is None
    assert _read({}) is None
    assert _read({"polls": [_poll(full=(5, 5))]}) is None
    still = _poll(full=(5, 5))
    assert _read({"polls": [still, still]}) is None
