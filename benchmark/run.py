#!/usr/bin/env python3
"""The benchmark's entry point.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, loads its configuration and traffic
files, and hands them to `benchmark/kinds/<kind>.py` (the traffic file names
the kind). Prints human-readable lines and then, as the last line of standard
output, one JSON object: correct, attempted, failed, metrics, device, and
with --trace 1 also breakdown. With --trace 0 the metrics are the cell's
end-to-end metrics, with --trace 1 its per-layer metrics, each read by
`benchmark/layer_metrics/<name>.py`.

This process never initialises a JAX backend: the chips belong to the
worker the runtime spawns. No chip, fewer chips than the cell asks for, no
`ray_tpu` beside this directory, or a worker on anything but a TPU: exit
code other than 0 and no result line.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def read_layer_metric(name: str, observed: dict):
    """`benchmark/layer_metrics/<name>.py` exposes `read(observed)`; a reader
    that finds nothing to read returns None and the metric is left out."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(observed)


def result_line(bench: dict, cell: dict, result: dict, trace: bool) -> dict:
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = result["end_to_end"][m["name"]]
    else:
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                value = read_layer_metric(m["name"], result["observed"])
                if value is not None:
                    metrics[m["name"]] = value
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": dict(result["device"]),
    }
    if trace:
        from benchmark import trace_reduce

        reduced = trace_reduce.reduce(result["observed"]["events"])
        if not reduced["busy_s"] > 0:
            raise SystemExit("the traced window holds no device operation")
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        print(f"[trace] devices={reduced['devices']} busy_s="
              f"{reduced['busy_s']:.4f} window_s={reduced['window_s']:.4f} "
              f"idle_share={reduced['idle_share']:.4f}", flush=True)
    # what `correct` compared, each number beside its limit: last in the
    # line, and the last lines on standard error
    line["compared"] = result.get("compared", {})
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return line


def wait_for_children(timeout: float = 60.0) -> None:
    """Every process this run started has ended before it returns: the
    runtime kills its workers without waiting, and a worker that still
    holds the chip would make the next run wait for it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            time.sleep(0.05)


def keep_trace(events, path: str, limit: int = 6000) -> None:
    """A look at the trace for whoever writes the next reader: every
    device operation's label with its total time, and the first `limit`
    events from 0.2 s into the first device's busy time on."""
    from collections import defaultdict

    from benchmark import trace_reduce

    planes = trace_reduce.device_planes(events)
    by_op = defaultdict(lambda: [0.0, 0])
    for e in events:
        if planes and e.plane == planes[0] and \
                e.line == trace_reduce.OPS_LINE:
            by_op[e.name][0] += e.dur_ns
            by_op[e.name][1] += 1
    start = min((e.start_ns for e in events
                 if planes and e.plane == planes[0]), default=0.0) + 2e8
    sample = sorted((e for e in events if e.start_ns >= start
                     and (e.line == trace_reduce.OPS_LINE
                          or not e.plane.startswith("/device:"))),
                    key=lambda e: e.start_ns)[:limit]
    lines = sorted({(e.plane, e.line) for e in events})
    with open(path, "w") as f:
        json.dump({"lines": lines,
                   "ops": sorted(([k, v[0] / 1e9, v[1]]
                                  for k, v in by_op.items()),
                                 key=lambda r: -r[1]),
                   "events": [list(e) for e in sample]}, f)


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE", default=None,
                    help="with --trace 1: also write the trace's device "
                         "operations by time and a sample of its events")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    os.chdir(ROOT)  # workers import `benchmark` and `ray_tpu` from here
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, traffic = load_cell(bench, args.workload)
    try:
        import ray_tpu
        from ray_tpu import accelerators, serve
    except ImportError as e:
        print(f"benchmark: ray_tpu is not importable from {ROOT}: {e}",
              file=sys.stderr)
        return 2
    chips = accelerators.TPUAcceleratorManager \
        .get_current_node_num_accelerators()
    if chips < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU "
              f"chip(s), this host has {chips}", file=sys.stderr)
        return 3
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    ray_tpu.init()
    try:
        result = kind.run(cell, config, traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=t_start)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        wait_for_children()
    device = result["device"]
    if device["platform"] != "tpu" or device["count"] != cell["chips"]:
        print(f"benchmark: ran on {device}, the cell needs "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        return 4
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("benchmark: the parent process initialised a JAX backend",
                  file=sys.stderr)
            return 5
    line = result_line(bench, cell, result, bool(args.trace))
    if args.trace and args.keep_trace:
        keep_trace(result["observed"]["events"], args.keep_trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
