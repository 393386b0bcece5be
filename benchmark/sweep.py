#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: one process, one set-up,
several arrival rates one after the other.

    python3 benchmark/sweep.py --workload serve-gpt2-large-chat-steady \
        --rates 1.0,1.4,1.8,2.2,2.6 --seconds 40 --seed 1

The knee is the highest rate at which the backlog (requests sent and not yet
finished) at the end of the window is no larger than at its middle; each is
the mean of readings taken four times a second over a fifth of the window
(0.4-0.6 and 0.8-1.0 of it), since one reading is a handful of requests
either way. The cell's traffic file then fixes `rate_rps` at 0.8 of it, by
hand. Not part of
a benchmark run; prints a table and one JSON line.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
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
    rows = []
    try:
        handle, ready_s = serve_kind.deploy(
            config, args.seed % (2 ** 31 - 1), "tpu")
        for rate in (float(r) for r in args.rates.split(",")):
            t = {**traffic, "rate_rps": rate, "preroll_s": 0.0}
            reqs = traffic_gen.open_loop(t, args.seed, args.seconds,
                                         config["vocab_size"])
            load_gen = serve_kind.Load(handle)
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

            def mean(lo, hi):
                xs = [n for f, n in readings if lo <= f < hi]
                return sum(xs) / len(xs)

            mid, end = mean(0.4, 0.6), mean(0.8, 1.01)
            stuck = load_gen.drain()
            ok = [r for r in load_gen.records if r["ok"]]
            ttft, gaps = serve_kind.latencies(ok, 0.0)
            rows.append({
                "rate_rps": rate, "sent": len(reqs), "ok": len(ok),
                "backlog_mid": mid, "backlog_end": end,
                "stuck": stuck, "late_ms": late * 1e3,
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p85_ms": stats.percentile(ttft, 85),
                "itl_p50_ms": stats.percentile(gaps, 50),
                "itl_p95_ms": stats.percentile(gaps, 95)})
            print(f"[sweep] {rows[-1]}", flush=True)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    print(json.dumps({"workload": args.workload, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
