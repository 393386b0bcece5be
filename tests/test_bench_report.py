"""Bench trajectory index drift gate.

Same contract as the dashboard gate in test_observability4: every
bench JSON artifact at the repo root must parse into a shape
``ray_tpu.devtools.bench_report`` understands, and the committed
BENCH_INDEX.md must byte-match a regeneration. Adding a bench round
without re-running ``python -m ray_tpu.devtools.bench_report`` fails
here, not three PRs later when someone reads a stale table."""

import glob
import json
import os

from ray_tpu.devtools import bench_report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_bench_artifact_parses():
    paths = (glob.glob(os.path.join(ROOT, "BENCH_r*.json"))
             + [os.path.join(ROOT, n) for n in
                ("CORE_BENCH.json", "SERVE_BENCH.json", "RL_BENCH.json")
                if os.path.exists(os.path.join(ROOT, n))])
    assert paths, "no bench artifacts found at the repo root"
    for p in paths:
        with open(p, encoding="utf-8") as f:
            json.load(f)  # raises on corruption
    data = bench_report.collect(ROOT)  # raises on unknown shape
    assert len(data["files"]) == len(paths)
    assert data["rounds"], "no bench rounds collected"
    for r in data["rounds"]:
        rec = r["record"]
        assert rec.get("metric") and rec.get("value") is not None, r


def test_index_has_every_artifact_and_primary_metric():
    text = bench_report.build_index(ROOT)
    data = bench_report.collect(ROOT)
    for name in data["files"]:
        assert name in text, f"{name} missing from index"
    for r in data["rounds"]:
        assert r["record"]["metric"] in text


def test_train_bubble_regression():
    """The interleaved-1F1B perf claim, gated on the committed bench
    artifact: in the newest round carrying the pipeline schedule-
    emulation A/B, the interleaved measured bubble must sit strictly
    below flat at equal S/M. (The emulated lane models op latency
    through the real driver/actor path, so the comparison is immune to
    single-core CPU contention — see bench.py `_pipeline_bench`.)"""
    check = bench_report.bubble_regression(ROOT)
    assert check is not None, (
        "no bench round records the pipeline emulation A/B — rerun "
        "`python bench.py` and commit the new BENCH_r<N>.json")
    assert check["ok"], (
        f"interleaved bubble regressed: {check['interleaved']} >= "
        f"{check['flat']} (flat) in {check['source']}")
    # the index surfaces the same verdict
    assert "Interleaved below flat (emulated lane): yes" in \
        bench_report.build_index(ROOT)


def test_zero_ladder_indexed():
    """The newest round's ZeRO ladder renders into the index with its
    byte-ratio summary — the bytes-win trajectory stays readable."""
    text = bench_report.build_index(ROOT)
    assert "## ZeRO ladder" in text
    assert "Sharded/replicated byte ratios" in text


def test_committed_index_matches_regeneration():
    committed = os.path.join(ROOT, "BENCH_INDEX.md")
    assert os.path.exists(committed), (
        "BENCH_INDEX.md missing — run "
        "`python -m ray_tpu.devtools.bench_report`")
    with open(committed, encoding="utf-8") as f:
        on_disk = f.read()
    assert on_disk == bench_report.build_index(ROOT), (
        "BENCH_INDEX.md is stale — regenerate with "
        "`python -m ray_tpu.devtools.bench_report`")
