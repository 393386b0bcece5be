"""CPU tests of the xing4 cell's readers of the residual streams' maps (PR
51): the required bytes against a hand count, the operations told by what
they return, and the two readers on a synthetic window
(`_xing4_window.py`). Run by hand with the rest of `benchmark/selftest`,
and by `tests/test_benchmark_selftests.py`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_mhc, mhc_ops  # noqa: E402
from benchmark.selftest import _xing4_window as w  # noqa: E402

reader = w.reader


def test_bytes_by_hand():
    # a row's state: 4 streams of 3584 in bf16; phi 14336 x 24 float32;
    # 24 logits in and out and 24 coefficients out, float32
    assert flops_mhc.maps_bytes(1, 4, 3584) == 28672 + 1376256 + 288
    assert flops_mhc.maps_bytes(256, 4, 3584) \
        == 256 * 28672 + 1376256 + 256 * 288
    assert flops_mhc.maps_flops(256, 4, 3584) == 2 * 256 * 14336 * 24
    # the whole half-layer's traffic a row: X four times, y once: 86 KB
    # a row where the mean square has no pass of its own (ISSUE 51)
    assert flops_mhc.half_layer_bytes(1, 4, 3584) - 1376256 == 121856
    best, bound = flops_mhc.program_least_seconds(w.config(), 256, w.KIND)
    assert bound == "memory"
    assert best == pytest.approx(12 * (256 * 28960 + 1376256) / 819e9)


def test_the_readers_on_a_synthetic_window():
    events, total_us, spent = w.window()
    obs = w.observed(events)
    found = mhc_ops.maps_ops(events, obs["config"])
    halves = 12 * 3  # a decode step and two chunks
    assert found["kernel"] == (pytest.approx(spent["kernel"] / 1e6), halves)
    assert found["product"] == (pytest.approx(spent["product"] / 1e6),
                                2 * halves)
    # the loops of 24 ROWS (f32[24,1,1,32,512]) are not the maps' 24
    took = spent["kernel"] + spent["product"]
    assert reader("mhc_share_pct")(obs) == pytest.approx(
        100 * took / total_us)
    best = 12 * (flops_mhc.maps_bytes(w.STEP_LANES, 4, 3584)
                 + 2 * flops_mhc.maps_bytes(w.CHUNK_ROWS, 4, 3584)) / 819e9
    assert reader("mhc_roofline_pct")(obs) == pytest.approx(
        100 * best / (took / 1e6))


def test_a_share_of_the_roofline_cannot_pass_100():
    at_peak = [12 * flops_mhc.maps_bytes(rows, 4, 3584) / 819e9 / 12 * 1e6
               for rows in (w.STEP_LANES, w.CHUNK_ROWS)]
    # the bias's microsecond and the kernel's beside the product's
    events, _, _ = w.window(maps_us=(1, 1), product_us=(
        at_peak[0] - 2, at_peak[1] - 2))
    assert reader("mhc_roofline_pct")(w.observed(events)) \
        == pytest.approx(100.0, rel=1e-3)


def test_no_reading_where_there_is_nothing_to_read():
    events, _, _ = w.window()
    assert reader("mhc_roofline_pct")(w.observed(events, False)) is None
    assert reader("mhc_share_pct")(w.observed(None)) is None
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.json")) as f:
        glm = json.load(f)
    assert mhc_ops.maps_ops(events, glm) is None
    for name in ("mhc_share_pct", "mhc_roofline_pct"):
        assert reader(name)({**w.observed(events), "config": glm}) is None
