"""CPU tests of `mla_decode_read_roofline_pct` (PR 52) on the labels of a
traced window whose decode steps read the dense latent kind with the Pallas
kernel: the synthetic window of `_xing4_window.py` with each decode step's
eight loops a layer replaced by the one custom call the v5e compile of the
xing4 cut's decode-32 holds (`ctx_read_paged.6 custom-call:tpu_custom_call
bf16[32,32,512]`: the AOT compile, PR 52). The arithmetic by hand, None
where there is nothing to read, and what `mla_dense_ops` still finds there:
the chunks' loops, and no decode step. Run by hand with the rest of
`benchmark/selftest`, and by `tests/test_benchmark_selftests.py`."""

import json
import os
import re

import pytest

from benchmark import mla_dense_ops
from benchmark.selftest import _xing4_window as w

reader = w.reader
KERNEL_US = 900.0
_STEP_LOOP = re.compile(r"while \(s32\[\], f32\[\d+,1,32,1\]")
_STEP_FOLD = re.compile(r"fusion f32\[\d+,1,1,32,512\]$")


def with_the_kernel(kernel_us=KERNEL_US):
    """`w.window()`'s events, a decode step's loops and what they cover
    taken out and one kernel call a layer put where the first stood."""
    events, out, at = w.window()[0], [], 6
    for e in events:
        if _STEP_FOLD.search(e.name):
            continue
        if not _STEP_LOOP.search(e.name):
            out.append(e)
        elif "f32[32,1,32,1]" in e.name:  # the step's first loop of eight
            out.append(w.op(f"ctx_read_paged.{at} custom-call:"
                            "tpu_custom_call bf16[32,32,512]",
                            e.start_ns / 1e3, kernel_us))
            at += 1
    return out


def test_the_share_by_hand():
    obs = w.observed(with_the_kernel())
    # one decode step of six layers at the window's mean of 24 lanes x
    # 9,000 slots, each slot's 576 lanes read once a layer at 819 GB/s
    least = 6 * w.STEP_SLOTS * 1152 / 819e9
    assert reader("mla_decode_read_roofline_pct")(obs) == pytest.approx(
        100 * least / (6 * KERNEL_US / 1e6))
    assert 30 < reader("mla_decode_read_roofline_pct")(obs) < 90


def test_a_kernel_at_the_hbm_peak_reads_90():
    """All 640 lanes of every row copied at the peak: 576 / 640."""
    at_peak = w.STEP_SLOTS * 1280 / 819e9 * 1e6
    obs = w.observed(with_the_kernel(at_peak))
    assert reader("mla_decode_read_roofline_pct")(obs) == pytest.approx(90.0)


def test_no_reading_where_there_is_nothing_to_read():
    read = reader("mla_decode_read_roofline_pct")
    # the parent's program: its decode steps read with the loops
    assert read(w.observed(w.window()[0])) is None
    # no trace, no edges, an engine without the counters
    assert read(w.observed(None)) is None
    assert read({**w.observed(with_the_kernel()), "before": None}) is None
    assert read(w.observed(with_the_kernel(), counters=False)) is None
    # another family's configuration that runs the same custom call
    root = os.path.join(w.ROOT, "benchmark", "configs")
    for name in ("glm-5.json", "gpt2-large.json"):
        with open(os.path.join(root, name)) as f:
            other = json.load(f)
        assert read({**w.observed(with_the_kernel()),
                     "config": other}) is None, name


def test_the_loops_reader_sees_the_chunks_alone():
    """`mla_dense_ops` on the same labels: the chunks' loops, 0 decode
    steps, and a share and a roofline share that are the chunks'."""
    events = with_the_kernel()
    obs = w.observed(events)
    found = mla_dense_ops.dense_ops(events, obs["config"])
    assert set(found) == {(1, 256)}
    assert mla_dense_ops.programs(found, obs["config"]) == (0.0, 2.0)
    chunk_us = 2 * 6 * 1500
    assert mla_dense_ops.seconds(found) == pytest.approx(chunk_us / 1e6)
    best = 2 * 6 * w.CHUNK_ROWS * w.CHUNK_START * 20480 / 197e12
    assert reader("mla_dense_roofline_pct")(obs) == pytest.approx(
        100 * best / (chunk_us / 1e6))
    assert reader("mla_dense_share_pct")(obs) > 0
