"""CPU tests of the xing4 cell's readers of the dense latent read (PR 51):
the required operations and bytes against hand-worked numbers, the
programs counted off the loops' carries, and the three readers on a
synthetic window (`_xing4_window.py`). Run by hand with the rest of
`benchmark/selftest`, and by `tests/test_benchmark_selftests.py`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import dsa_ops, flops_mla_dense, mla_dense_ops  # noqa: E402
from benchmark.selftest import _xing4_window as w  # noqa: E402

reader = w.reader


def test_flops_and_bytes_by_hand():
    # one (row, slot) pair: 32 heads, q.k over 192 lanes, p.v over 128
    assert flops_mla_dense.read_flops(1, 32, 128, 64, 128) == 32 * 2 * 320
    assert flops_mla_dense.read_bytes(1000, 512, 64) == 1_152_000
    cfg = w.config()
    # a decode step of 24 lanes at 9,000 slots is bound by memory
    best, bound = flops_mla_dense.program_least_seconds(
        cfg, 24 * 9000, 24 * 9000, w.KIND)
    assert bound == "memory"
    assert best == pytest.approx(6 * 24 * 9000 * 1152 / 819e9, rel=1e-6)
    # a chunk of 256 rows at 8,192 slots by the per-head products
    best, bound = flops_mla_dense.program_least_seconds(
        cfg, 256 * 8192, 8192, w.KIND)
    assert bound == "compute"
    assert best == pytest.approx(6 * 256 * 8192 * 20480 / 197e12, rel=1e-6)
    # the absorbed form's products are 3.4 times the required ones
    assert (2 * 512 + 64) / 320 == pytest.approx(3.4)


@pytest.mark.parametrize("rows,groups", [(1, 1), (2, 1), (4, 1), (8, 2),
                                         (16, 2), (32, 4)])
def test_a_decode_step_of_any_bucket_counts_once(rows, groups):
    """The loops of one decode step of `rows` rows (a loop a group of
    lanes, over rows, rows - g, ..., g) in six layers are one step."""
    cfg = w.config()
    found = {(b, 1): (1e-4, 6) for b in range(rows, 0, -groups)}
    found[(1, 256)] = (1e-3, 18)  # and three chunks
    assert mla_dense_ops.programs(found, cfg) == (1.0, 3.0)


def test_the_readers_on_a_synthetic_window():
    events, total_us, spent = w.window()
    obs = w.observed(events)
    cfg = obs["config"]
    found = mla_dense_ops.dense_ops(events, cfg)
    assert set(found) == {(b, 1) for b in range(32, 0, -4)} | {(1, 256)}
    assert mla_dense_ops.seconds(found) == pytest.approx(spent["read"] / 1e6)
    assert mla_dense_ops.programs(found, cfg) == (1.0, 2.0)
    share = reader("mla_dense_share_pct")(obs)
    assert share == pytest.approx(100 * spent["read"] / total_us)
    mean = mla_dense_ops.window_means(obs)
    assert mean["decode"] == {"rows": w.STEP_LANES, "pairs": w.STEP_SLOTS,
                              "slots": w.STEP_SLOTS}
    assert mean["prefill"] == {"rows": w.CHUNK_ROWS,
                               "pairs": w.CHUNK_ROWS * w.CHUNK_START,
                               "slots": w.CHUNK_START}
    best = 6 * w.STEP_SLOTS * 1152 / 819e9 \
        + 2 * 6 * w.CHUNK_ROWS * w.CHUNK_START * 20480 / 197e12
    assert reader("mla_dense_roofline_pct")(obs) == pytest.approx(
        100 * best / (spent["read"] / 1e6))
    assert reader("mla_ctx_slots_per_row")(obs) == pytest.approx(9000)


def test_a_share_of_the_roofline_cannot_pass_100():
    """Loops as fast as the chip's peaks allow read 100%: the per-head
    products are what no form undercuts, so an absorbed read stays under
    a third of that."""
    cfg = w.config()
    step = 6 * w.STEP_SLOTS * 1152 / 819e9 / 6 / 8 * 1e6  # a loop's least
    chunk = w.CHUNK_ROWS * w.CHUNK_START * 20480 / 197e12 * 1e6
    events, _, _ = w.window(read_us=(step, chunk))
    assert reader("mla_dense_roofline_pct")(w.observed(events)) \
        == pytest.approx(100.0, rel=1e-3)
    events, _, _ = w.window(read_us=(step, 3.4 * chunk))
    assert reader("mla_dense_roofline_pct")(w.observed(events)) < 100.0


def test_no_reading_where_there_is_nothing_to_read():
    events, _, _ = w.window()
    for name in ("mla_dense_share_pct", "mla_dense_roofline_pct",
                 "mla_ctx_slots_per_row"):
        read = reader(name)
        # the parent's engine: no such counters
        old = w.observed(events, counters=False)
        if name != "mla_dense_share_pct":
            assert read(old) is None, name
        # no trace, or no edges
        assert read({**w.observed(None), "before": None}) is None, name
    # another family's configuration: the other latent kind's, a dense one
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.json")) as f:
        glm = json.load(f)
    assert mla_dense_ops.dense_ops(events, glm) is None
    assert reader("mla_dense_share_pct")(
        {**w.observed(events), "config": glm}) is None
    # and glm-5's reader does not take this cell's loops for its own
    assert dsa_ops.latent_ops(events, w.config()) is None
