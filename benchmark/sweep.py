#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: one process, one set-up,
several arrival rates one after the other, lowest first.

    python3 benchmark/sweep.py --workload serve-gpt2-large-chat-steady \
        --rates 8,12,16,20,25,30,40 --seconds 60 --seed 1

The backlog (requests sent and not yet finished) is read four times a second;
its mean over 0.4-0.6 of the window is the middle's, over 0.8-1.0 the end's,
since one reading is a handful of requests either way. `backlog_trend` calls
a rate `growing` where the end's lies above the middle's by more than a
quarter of it and by more than one request, `falling` where it lies as far
below, `level` between. A rate is past the knee where its backlog grows, and
by nothing else. `knee` names the highest rate under the lowest one that grew,
and names none where no rate did: a sweep whose highest rate still has a level
or falling backlog has not found a knee. The cell's traffic file then fixes
`rate_rps` at 0.8 of it, by hand, with the readings in `knee_from`; a file
whose rate lies lower says why in `rate_why`. The token gaps at the client and
where the engine emits them are printed beside each rate as readings (how far
the hand-off of streamed items queues below the knee); the rule does not read
them. A knee at which the generator runs late (`late_ms`) is the harness's,
not the engine's. The sweep stops by itself once `--stop-after`
rates in a row have grown (the queue of a rate far above the knee takes
minutes to drain). Not part of a benchmark run; prints a table and one JSON
line, and writes that line to `chiprun_out/sweep-<workload>.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

GROWS_BY_SHARE, GROWS_BY_REQUESTS = 0.25, 1.0


def backlog_trend(mid: float, end: float) -> str:
    """`growing`, `level` or `falling`: the end's backlog against the
    middle's, beyond a quarter of it and one request."""
    slack = max(GROWS_BY_REQUESTS, GROWS_BY_SHARE * mid)
    if end - mid > slack:
        return "growing"
    if mid - end > slack:
        return "falling"
    return "level"


def knee(rows: list[dict]) -> dict:
    """The knee of a sweep's rows (`rate_rps`, `backlog_mid`, `backlog_end`):
    {"knee_rps": the highest rate under the lowest one whose backlog grew, or
    None; "above": the rates that grew; "why": one line}."""
    rows = sorted(rows, key=lambda r: r["rate_rps"])
    above = sorted({r["rate_rps"] for r in rows if backlog_trend(
        r["backlog_mid"], r["backlog_end"]) == "growing"})
    if not above:
        return {"knee_rps": None, "above": [],
                "why": "no rate's backlog grew: the sweep has not reached "
                       "the knee, sweep higher rates"}
    under = sorted({r["rate_rps"] for r in rows if r["rate_rps"] < above[0]})
    if not under:
        return {"knee_rps": None, "above": above,
                "why": f"the lowest rate swept, {above[0]}, already grew: "
                       "sweep lower rates"}
    return {"knee_rps": under[-1], "above": above,
            "why": f"growing at {above[0]}, not at {under[-1]}"}


def window_account(before: dict, after: dict) -> dict:
    """What the engine did between two `engine_stats()`: decode steps, the
    tokens a decode step emitted (its lanes), a decode turn's mean, and the
    token gaps' percentiles where the loop emits them (the stall line's)."""
    from benchmark import stall

    pooled = stall.line(before, after, after, 0.0, None)["gaps"]["pooled"]
    steps = after["steps"].get("decode", 0) - before["steps"].get("decode", 0)
    out = {"decode_steps": steps,
           "lanes_per_decode_step": pooled["n"] / steps if steps else None,
           "engine_itl_p50_ms": pooled["p50"],
           "engine_itl_p95_ms": pooled["p95"]}
    turn = after["loop"]["turns"].get("decode")
    was = before["loop"]["turns"].get("decode")
    if turn and was and turn["count"] > was["count"]:
        out["decode_turn_mean_ms"] = 1e3 * (turn["wall_s"] - was["wall_s"]) \
            / (turn["count"] - was["count"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--stop-after", type=int, default=2,
                    help="stop once this many rates in a row have grown")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    import ray_tpu
    from benchmark import stats, traffic_gen
    from benchmark.kinds import serve as serve_kind
    from benchmark.run import load_cell
    from ray_tpu import serve

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, config, traffic = load_cell(json.load(f), args.workload)
    ray_tpu.init()
    rows, grown = [], 0
    try:
        handle, ready_s = serve_kind.deploy(
            config, args.seed % (2 ** 31 - 1), "tpu")
        for rate in sorted(float(r) for r in args.rates.split(",")):
            t = {**traffic, "rate_rps": rate, "preroll_s": 0.0}
            reqs = traffic_gen.open_loop(t, args.seed, args.seconds,
                                         config["vocab_size"])
            load_gen = serve_kind.Load(handle)
            before = serve_kind.replica_call("engine_stats")
            t0 = time.monotonic() + 0.05
            readings = []

            def poll():
                while time.monotonic() < t0 + args.seconds:
                    readings.append(((time.monotonic() - t0) / args.seconds,
                                     load_gen.in_flight()))
                    time.sleep(0.25)

            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            late = load_gen.open_loop(reqs, t0)
            poller.join()
            after = serve_kind.replica_call("engine_stats")

            def mean(lo, hi):
                xs = [n for f, n in readings if lo <= f < hi]
                return sum(xs) / len(xs)

            mid, end = mean(0.4, 0.6), mean(0.8, 1.01)
            t_drain = time.monotonic()
            stuck = load_gen.drain()
            ok = [r for r in load_gen.records if r["ok"]]
            ttft, gaps = serve_kind.latencies(ok, 0.0)
            rows.append({
                "rate_rps": rate, "sent": len(reqs), "ok": len(ok),
                "backlog_mid": mid, "backlog_end": end,
                "trend": backlog_trend(mid, end),
                "stuck": stuck, "late_ms": late * 1e3,
                "drain_s": time.monotonic() - t_drain,
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p85_ms": stats.percentile(ttft, 85),
                "itl_p50_ms": stats.percentile(gaps, 50),
                "itl_p95_ms": stats.percentile(gaps, 95),
                **window_account(before, after)})
            print(f"[sweep] {rows[-1]}", flush=True)
            grown = grown + 1 if rows[-1]["trend"] == "growing" else 0
            if grown >= args.stop_after or stuck:
                break
        memory_peak = serve_kind.replica_call("device_memory")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    line = json.dumps({"workload": args.workload, "seconds": args.seconds,
                       "seed": args.seed, "ready_s": ready_s,
                       "memory_peak_bytes": memory_peak,
                       "knee": knee(rows), "rows": rows})
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep-{args.workload}.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
