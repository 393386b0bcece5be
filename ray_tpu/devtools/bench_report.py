"""Bench trajectory index: merge every bench artifact the repo has
accumulated into one chronological markdown table.

The repo's bench history lives in heterogeneous JSON artifacts:
``BENCH_rNN``/``SERVE_BENCH``/``RL_BENCH`` put the primary metric at
the top level with ``secondary_metrics``, and ``CORE_BENCH`` is a flat
``{scenario: {value, unit, baseline, vs_baseline}}`` map. Nobody can
read a trajectory off several shapes — this module can, and regenerates
``BENCH_INDEX.md`` from them:

    python -m ray_tpu.devtools.bench_report            # writes BENCH_INDEX.md
    python -m ray_tpu.devtools.bench_report -o - --root .   # stdout

``tests/test_bench_report.py`` gates the index the same way the metric
catalog gates the dashboard: every bench JSON must parse into a known
shape, and the committed BENCH_INDEX.md must match a regeneration."""

from __future__ import annotations

import glob
import json
import os

_HEADER = (
    "# Bench trajectory index\n\n"
    "Generated by `python -m ray_tpu.devtools.bench_report` from the\n"
    "bench JSON artifacts at the repo root — regenerate after adding a\n"
    "bench round; `tests/test_bench_report.py` fails on drift. Do not\n"
    "edit by hand.\n")


def _fmt(v) -> str:
    if v is None or v == "":
        return "-"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _primary(doc: dict) -> dict | None:
    """The {metric, value, unit, vs_baseline, secondary_metrics} record
    of a train/serve/rl bench doc."""
    return doc if "metric" in doc else None


def collect(root: str) -> dict:
    """Parse every bench artifact under ``root`` into
    ``{"rounds": [...], "core": [...], "files": [names]}``. Raises on unparseable JSON or an artifact
    matching no known shape — the drift gate wants loud failures."""
    rounds: list[dict] = []
    core: list[dict] = []
    files: list[str] = []

    def load(path):
        files.append(os.path.basename(path))
        with open(path, encoding="utf-8") as f:
            return json.load(f)

    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        doc = load(path)
        name = os.path.basename(path)
        rec = _primary(doc)
        if rec is None:
            raise ValueError(f"{name}: no primary metric")
        rounds.append({"source": name, "lane": "train", "record": rec})
    for name, lane in (("SERVE_BENCH.json", "serve"),
                       ("RL_BENCH.json", "rl")):
        path = os.path.join(root, name)
        if not os.path.exists(path):
            continue
        rec = _primary(load(path))
        if rec is None:
            raise ValueError(f"{name}: no primary metric")
        rounds.append({"source": name, "lane": lane, "record": rec})

    path = os.path.join(root, "CORE_BENCH.json")
    if os.path.exists(path):
        doc = load(path)
        for scenario, row in sorted(doc.items()):
            if scenario == "environment":  # free-form host metadata
                continue
            if not isinstance(row, dict) or "value" not in row:
                raise ValueError(f"CORE_BENCH.json: bad row {scenario!r}")
            core.append({"scenario": scenario, **row})

    return {"rounds": rounds, "core": core, "files": files}


def bubble_regression(root: str | None = None) -> dict | None:
    """The `train-bubble-regression` check: from the newest bench round
    carrying the pipeline schedule-emulation A/B, the interleaved
    measured bubble must sit strictly below flat at equal S/M —
    `tests/test_bench_report.py` gates `ok`. Returns None when no
    round has recorded the A/B yet (pre-interleaved artifacts)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                       reverse=True):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        rec = _primary(doc) or {}
        emu = ((rec.get("extra") or {}).get("pipeline") or {}).get(
            "emulated")
        if emu:
            flat = emu.get("flat_bubble")
            inter = emu.get("interleaved_bubble")
            return {"source": os.path.basename(path), "flat": flat,
                    "interleaved": inter,
                    "ok": (flat is not None and inter is not None
                           and inter < flat)}
    return None


def _zero_ladder_section(data: dict) -> list[str]:
    """Render the newest train round's ZeRO ladder A/B (stages 0..3
    per-chip state bytes + throughput), if any round recorded one."""
    for r in reversed(data["rounds"]):
        if r["lane"] != "train" or not r["record"]:
            continue
        zl = (r["record"].get("extra") or {}).get("zero_ladder") or {}
        if not zl.get("ratios"):
            continue
        out = [f"\n## ZeRO ladder ({r['source']})\n"]
        out.append("Stages 0..3 of the same step (adamw, accum_steps="
                   f"{zl.get('accum_steps', '-')}, data axis "
                   f"{zl.get('data_axis', '-')}): each rung shards one "
                   "more state component 1/N.\n")
        out.append("| Stage | tokens/s | opt bytes/chip "
                   "| grad bytes/chip | param bytes/chip |")
        out.append("|-------|----------|----------------"
                   "|-----------------|------------------|")
        for s in range(4):
            row = zl.get(f"stage{s}") or {}
            if not row:
                continue
            out.append(
                f"| {s} | {_fmt(row.get('tokens_per_sec'))} "
                f"| {_fmt(row.get('opt_bytes'))} "
                f"| {_fmt(row.get('grad_bytes'))} "
                f"| {_fmt(row.get('param_bytes'))} |")
        ratios = zl["ratios"]
        out.append(
            f"\nSharded/replicated byte ratios: optimizer "
            f"{_fmt(ratios.get('opt_bytes'))} (stage 1), grads "
            f"{_fmt(ratios.get('grad_bytes'))} (stage 2), params "
            f"{_fmt(ratios.get('param_bytes'))} (stage 3); max loss "
            f"delta vs stage 0: {_fmt(zl.get('loss_delta_max'))}.")
        return out
    return []


def _pipeline_section(data: dict) -> list[str]:
    """Render the newest train round's flat-vs-interleaved pipeline
    A/B: real-compute lane plus the schedule-emulation lane the
    `train-bubble-regression` check gates."""
    for r in reversed(data["rounds"]):
        if r["lane"] != "train" or not r["record"]:
            continue
        pipe = (r["record"].get("extra") or {}).get("pipeline") or {}
        emu = pipe.get("emulated") or {}
        if not emu:
            continue
        inter = pipe.get("interleaved") or {}
        out = [f"\n## Pipeline schedule A/B ({r['source']})\n"]
        out.append(f"Flat vs interleaved 1F1B at equal "
                   f"S={_fmt(pipe.get('stages'))}/"
                   f"M={_fmt(pipe.get('microbatches'))}. The emulated "
                   "lane models op latency through the real "
                   "driver/actor path (schedule-quality bubble, "
                   "immune to host CPU contention); the real lane is "
                   "actual XLA compute on this host.\n")
        out.append("| Schedule | Lane | Bubble | Theoretical "
                   "| Step ms |")
        out.append("|----------|------|--------|-------------"
                   "|---------|")
        out.append(
            f"| flat | real | {_fmt(pipe.get('bubble_ratio'))} "
            f"| {_fmt(pipe.get('bubble_theoretical'))} "
            f"| {_fmt(pipe.get('step_ms'))} |")
        out.append(
            f"| interleaved | real | {_fmt(inter.get('bubble_ratio'))} "
            f"| {_fmt(inter.get('bubble_theoretical'))} "
            f"| {_fmt(inter.get('step_ms'))} |")
        out.append(
            f"| flat | emulated | {_fmt(emu.get('flat_bubble'))} "
            f"| {_fmt(emu.get('flat_theoretical'))} | - |")
        out.append(
            f"| interleaved | emulated "
            f"| {_fmt(emu.get('interleaved_bubble'))} "
            f"| {_fmt(emu.get('interleaved_theoretical'))} | - |")
        wins = "yes" if emu.get("interleaved_wins") else "NO"
        out.append(f"\nInterleaved below flat (emulated lane): {wins} "
                   "— the `train-bubble-regression` gate in "
                   "tests/test_bench_report.py.")
        return out
    return []


def render(data: dict) -> str:
    out = [_HEADER]

    out.append("\n## Bench rounds (train / serve / RL lanes)\n")
    out.append("Primary metric per round, secondary metrics indented "
               "beneath it.\n")
    out.append("| Source | Lane | Metric | Value | Unit | vs baseline |")
    out.append("|--------|------|--------|-------|------|-------------|")
    for r in data["rounds"]:
        rec = r["record"]
        out.append(
            f"| {r['source']} | {r['lane']} | {rec.get('metric', '-')} "
            f"| {_fmt(rec.get('value'))} | {rec.get('unit', '-')} "
            f"| {_fmt(rec.get('vs_baseline'))} |")
        for s in rec.get("secondary_metrics") or ():
            out.append(
                f"| | | &nbsp;&nbsp;{s.get('metric', '-')} "
                f"| {_fmt(s.get('value'))} | {s.get('unit', '-')} "
                f"| {_fmt(s.get('vs_baseline'))} |")

    out.extend(_zero_ladder_section(data))
    out.extend(_pipeline_section(data))

    if data["core"]:
        out.append("\n## Core microbenchmarks (CORE_BENCH.json)\n")
        out.append("| Scenario | Value | Unit | Baseline | vs baseline |")
        out.append("|----------|-------|------|----------|-------------|")
        for c in data["core"]:
            out.append(
                f"| {c['scenario']} | {_fmt(c.get('value'))} "
                f"| {c.get('unit', '-')} | {_fmt(c.get('baseline'))} "
                f"| {_fmt(c.get('vs_baseline'))} |")

    out.append(f"\n{len(data['files'])} artifacts indexed: "
               + ", ".join(data["files"]) + "\n")
    return "\n".join(out)


def build_index(root: str | None = None) -> str:
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    return render(collect(root))


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m ray_tpu.devtools.bench_report")
    ap.add_argument("-o", "--output", default=None,
                    help="output path ('-' for stdout); default "
                         "<root>/BENCH_INDEX.md")
    ap.add_argument("--root", default=None,
                    help="directory holding the bench JSONs "
                         "(default: the repo root)")
    args = ap.parse_args(argv)
    root = args.root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    text = build_index(root)
    out = args.output or os.path.join(root, "BENCH_INDEX.md")
    if out == "-":
        print(text, end="")
        return 0
    with open(out, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"wrote {out} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
