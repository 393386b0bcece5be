"""Runs any serve cell from (configuration file, traffic file): open or closed
loop, as the traffic file says.

The parent process stays off JAX. It deploys the LLM application with
`serve.run(...)` through the same `serve.deployment(...)` arguments as
`ray_tpu.serve.llm.build_llm_app`, and every request goes through
`handle.options(stream=True).remote(payload)`: router, replica, engine,
token stream. The replica, a worker the runtime spawned, owns the chip.

The deployed class is `BenchLLMServer`, `LLMServer` plus five methods that
the served path never calls: install weights made from the run's seed
(through the engine's own `update_weights`), start and stop a profiler trace,
report the device's peak memory (the engine's stats carry no memory reading),
and run the plain reference on the engine's weights after the window. Only
the process that owns the chip can do any of these.
"""

from __future__ import annotations

import itertools
import shutil
import tempfile
import threading
import time

from benchmark import stall, stats, traffic_gen
from benchmark.device import memory_peak, profiler_options
from benchmark.model_api import load, sizes
from ray_tpu.serve.llm.deployment import LLMServer

APP = "bench-llm"
CHECK_PROMPT_LENS = (24, 64, 130, 300)  # the last crosses the 256 chunk
CHECK_MAX_TOKENS = 8
# 2 s of profile: at 32 lanes and long contexts the profiler's events of
# 4 s took `trace_stop` past the harness's patience (306.7 s in long-decode
# at PR 36). The trace is the window's last TRACE_FOR_S seconds and ends with
# it, so that `trace_stop`, which slows every step of the replica for as long
# as it runs (a decode turn 9.7 ms where 7.5; my chip runs, PR 43), starts
# once the "after" snapshot is taken and runs in the run's tail alone, outside
# the window whose counters the readers take. It runs in a thread of its own
TRACE_FOR_S = 2.0
TRACE_STOP_TIMEOUT_S = 300.0
POLL_S = 0.5
DRAIN_TIMEOUT_S = 90.0


class BenchLLMServer(LLMServer):
    def load_seeded_weights(self, seed: int) -> dict:
        """Weights from the run's seed, installed by the engine's hot swap.
        The engine itself is built with a fixed `EngineConfig.seed`: the
        runner closes its sampling key (seed + 1) into every program as a
        constant, so an engine seed that followed `--seed` would compile all
        14 programs anew in every run with a new seed (16 s; my chip runs,
        PR 24) instead of finding them in the cache."""
        import jax

        from ray_tpu.serve.llm.runner import adapters

        engine = self.engine
        params = adapters()[engine.config.model].init_fn(
            jax.random.PRNGKey(seed), engine.model_cfg)
        return engine.update_weights(1, params)

    def trace_start(self, trace_dir: str) -> bool:
        import jax

        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profiler_options())
        return True

    def trace_stop(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True

    def reference_logprobs(self, spec: str, model: dict, cases: list):
        """The configuration's plain reference (`module:function`) on the
        weights this engine serves, in float32 at highest precision."""
        return load(spec)(self.engine.runner.params, model, cases)

    def device_memory(self) -> int:
        import jax

        return max(memory_peak(d.memory_stats()) for d in jax.local_devices())


def replica_call(method: str, *args, timeout: float = 120.0):
    """Call a method of the (one) replica on its control concurrency group,
    as `state.llm_status` does, so it does not queue behind streams."""
    import ray_tpu
    from ray_tpu.serve.api import _CONTROLLER_NAME

    ctrl = ray_tpu.get_actor(_CONTROLLER_NAME)
    reps = ray_tpu.get(ctrl.get_replicas.remote(APP), timeout=30)["replicas"]
    if len(reps) != 1:
        raise RuntimeError(f"{len(reps)} replicas, expected one")
    return ray_tpu.get(reps[0].handle_request.options(
        concurrency_group="control").remote(method, args, {}),
        timeout=timeout)


def deploy(config: dict, weight_seed: int, platform: str):
    """serve.run(...) of the configuration; returns (handle, seconds until
    the handle answered)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm.config import EngineConfig

    model = config["model"]
    cfg = {"model": model["family"], "preset": model["preset"],
           **config["engine"], "seed": 0}
    EngineConfig.from_dict(cfg)  # validate here, not in the replica
    dep = serve.deployment(
        BenchLLMServer, name=f"llm-{cfg['model']}",
        num_replicas=config["deployment"]["num_replicas"],
        max_ongoing_requests=config["deployment"]["max_ongoing_requests"],
        ray_actor_options={"num_tpus": 1} if platform == "tpu" else None,
        payload_affinity=True)
    t0 = time.monotonic()
    handle = serve.run(dep.bind(cfg), name=APP)
    ray_tpu.get(handle.method("ping")(), timeout=120)
    ready_s = time.monotonic() - t0
    swap = replica_call("load_seeded_weights", weight_seed, timeout=300)
    print(f"[serve] replica ready after {ready_s:.1f} s; seeded weights "
          f"installed in {time.monotonic() - t0 - ready_s:.1f} s "
          f"(swap {swap['swap_seconds']:.2f} s)", flush=True)
    return handle, ready_s


class Load:
    """Sends requests through the handle and records, on this process's
    clock, when each token arrived."""

    def __init__(self, handle):
        self.handle = handle
        self.records: list[dict] = []
        self.threads: list[threading.Thread] = []
        self._lock = threading.Lock()

    def request(self, req: traffic_gen.Request, due: float | None,
                logprobs: bool = False) -> dict:
        """One streamed request, start to end, in the calling thread."""
        import ray_tpu

        rec = {"measured": req.measured, "due": due,
               "sent": time.monotonic(), "n_prompt": len(req.prompt),
               "max_tokens": req.max_tokens, "times": [], "tokens": [],
               "logprobs": [], "ok": False, "error": None, "end": None}
        with self._lock:
            self.records.append(rec)
        try:
            payload = {"prompt": req.prompt, "max_tokens": req.max_tokens,
                       "temperature": 0.0}
            if logprobs:
                payload["logprobs"] = True
            final = None
            for ref in self.handle.options(stream=True).remote(payload):
                ev = ray_tpu.get(ref, timeout=DRAIN_TIMEOUT_S)
                now = time.monotonic()
                if ev.get("done"):
                    final = ev
                    break
                rec["times"].append(now)
                rec["tokens"].append(ev["token"])
                if logprobs:
                    rec["logprobs"].append(ev["logprob"])
            rec["end"] = time.monotonic()
            if final is None:
                rec["error"] = "stream ended without a final event"
            elif final.get("finish_reason") != "length":
                rec["error"] = f"finish_reason {final.get('finish_reason')}"
            elif rec["tokens"] != list(final["token_ids"]) \
                    or len(rec["tokens"]) != req.max_tokens:
                rec["error"] = "streamed tokens differ from the final event"
            else:
                rec["ok"] = True
        except Exception as e:  # noqa: BLE001 - a failed request is a datum
            rec["end"] = time.monotonic()
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    def open_loop(self, requests, t0: float) -> float:
        """Send each request when it is due (t0 + due_s), each in a thread
        of its own. Returns the worst lateness of a send, in seconds."""
        late = 0.0
        for req in requests:
            due = t0 + req.due_s
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            late = max(late, time.monotonic() - due)
            th = threading.Thread(target=self.request, args=(req, due),
                                  daemon=True)
            th.start()
            self.threads.append(th)
        return late

    def closed_loop(self, pool, clients: int, t_stop: float):
        """`clients` callers, each sending its next request when the last
        one ended, until t_stop; requests are taken from the pool in
        order."""
        counter = itertools.count()

        def client():
            while time.monotonic() < t_stop:
                with self._lock:
                    i = next(counter)
                self.request(pool.get(i), None)

        for _ in range(clients):
            th = threading.Thread(target=client, daemon=True)
            th.start()
            self.threads.append(th)

    def drain(self) -> int:
        """Wait for every request in flight; returns how many never ended."""
        deadline = time.monotonic() + DRAIN_TIMEOUT_S
        for th in self.threads:
            th.join(timeout=max(0.0, deadline - time.monotonic()))
        return sum(th.is_alive() for th in self.threads)

    def in_flight(self) -> int:
        with self._lock:
            return sum(r["end"] is None for r in self.records)


def credited_events(records, t0: float, t1: float):
    """(time, tokens) credits inside [t0, t1]: a request's prompt at its
    first streamed token, each output token at its own arrival."""
    times, amounts = [], []
    for r in records:
        for i, t in enumerate(r["times"]):
            if t0 <= t <= t1:
                times.append(t)
                amounts.append(1 + (r["n_prompt"] if i == 0 else 0))
    return times, amounts


def latencies(records, worst_ms: float) -> tuple[list, list]:
    """(time to first token of each request, from when it was due; gaps
    between consecutive tokens of the requests that succeeded), in ms. A
    failed request's time to first token is `worst_ms`."""
    ttft = [(r["times"][0] - r["due"]) * 1e3 if r["ok"] else worst_ms
            for r in records]
    gaps = [(b - a) * 1e3 for r in records if r["ok"]
            for a, b in zip(r["times"], r["times"][1:])]
    return ttft, gaps


def _watch(t0: float, seconds: float, trace_dir: str | None,
           observed: dict):
    """Every run's side thread: the engine's stats at the window's two
    edges ("before", "after": taken when the window opens and when it ends,
    whatever else is still going on, so that what a reader takes as the
    window's delta holds nothing of the run's tail). In the traced run
    (`trace_dir`) also the metrics page at both edges, the stats polled
    twice a second, and a profiler trace of the window's last TRACE_FOR_S
    seconds, started and stopped by a thread of its own: `trace_stop` waits
    for "after", may take minutes, and neither the polls nor "after" wait
    for it."""
    from ray_tpu.util import state

    def snapshot():
        snap = {"stats": replica_call("engine_stats")}
        if trace_dir:
            snap["page"] = state.cluster_metrics()
        return snap

    def trace():
        time.sleep(max(0.0, t0 + seconds - TRACE_FOR_S - time.monotonic()))
        replica_call("trace_start", trace_dir)
        time.sleep(TRACE_FOR_S)
        after_taken.wait(timeout=30.0)  # the window's delta holds no stop
        t_stop = time.monotonic()
        replica_call("trace_stop", timeout=TRACE_STOP_TIMEOUT_S)
        observed["trace_stop_s"] = time.monotonic() - t_stop

    after_taken = threading.Event()
    time.sleep(max(0.0, t0 - time.monotonic()))
    observed["before"] = snapshot()
    tracer = threading.Thread(target=trace, daemon=True) if trace_dir \
        else None
    if tracer:
        tracer.start()
    polls = []
    while tracer and time.monotonic() < t0 + seconds - POLL_S:
        polls.append(replica_call("engine_stats"))
        time.sleep(POLL_S)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    observed["after"] = snapshot()
    after_taken.set()
    observed["after_late_s"] = time.monotonic() - (t0 + seconds)
    if tracer:
        observed["polls"] = polls
        tracer.join()


def check_outputs(load_gen: Load, config: dict, seed: int,
                  vocab: int) -> tuple[bool, float]:
    """Four seeded requests with log-probs, against the plain reference's
    log-softmax at the streamed tokens (run in the replica, after the
    window, on the same weights). Returns (ok, worst |diff| in nats)."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    cases = []
    for n in CHECK_PROMPT_LENS:
        req = traffic_gen.Request(None, rng.integers(1, vocab, n).tolist(),
                                  CHECK_MAX_TOKENS, False)
        rec = load_gen.request(req, None, logprobs=True)
        if not rec["ok"]:
            print(f"[serve] check request failed: {rec['error']}", flush=True)
            return False, float("nan")
        cases.append({"prompt": req.prompt, "tokens": rec["tokens"],
                      "logprobs": rec["logprobs"]})
    want = replica_call(
        "reference_logprobs", config["model"]["reference"], sizes(config),
        [{"prompt": c["prompt"], "tokens": c["tokens"]} for c in cases],
        timeout=900)
    worst = max(abs(a - b) for c, w in zip(cases, want)
                for a, b in zip(c["logprobs"], w))
    return worst <= config["logprob_tolerance"], worst


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float,
        platform: str = "tpu") -> dict:
    weight_seed = seed % (2 ** 31 - 1)
    vocab = config["vocab_size"]
    handle, ready_s = deploy(config, weight_seed, platform)
    load_gen = Load(handle)
    preroll = float(traffic.get("preroll_s", 0.0))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    observed: dict = {}
    try:
        t0 = time.monotonic() + preroll + 0.05
        watcher = threading.Thread(
            target=_watch, args=(t0, seconds, trace_dir, observed),
            daemon=True)
        watcher.start()
        ticker = stall.Ticker(t0, t0 + seconds).start()
        if traffic["loop"] == "open":
            requests = traffic_gen.open_loop(traffic, seed, seconds, vocab)
            late = load_gen.open_loop(requests, t0)
        elif traffic["loop"] == "closed":
            pool = traffic_gen.ClosedPool(traffic, seed, vocab)
            load_gen.closed_loop(pool, traffic["clients"], t0 + seconds)
            late = 0.0
        else:
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        backlog_end = load_gen.in_flight()
        stuck = load_gen.drain()
        watcher.join(timeout=600)
        ticker.join()
        stats_now = replica_call("engine_stats")
        stalled = stall.report(
            observed.get("before", {}).get("stats"),
            observed.get("after", {}).get("stats"), stats_now, late, ticker,
            observed.get("polls"), hist=trace)
        memory_peak = replica_call("device_memory")
        t_check = time.monotonic()
        ok_ref, worst = check_outputs(load_gen, config, seed, vocab)
        print(f"[serve] output check took {time.monotonic() - t_check:.1f} s"
              + (f"; trace_stop took {observed['trace_stop_s']:.1f} s"
                 if "trace_stop_s" in observed else "")
              + f"; the window's last snapshot came "
              f"{observed.get('after_late_s', float('nan')):.3f} s after "
              "its end", flush=True)
        events = None
        if trace:
            from benchmark import trace_reduce

            events = trace_reduce.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if traffic["loop"] == "open":
        measured = [r for r in load_gen.records if r["measured"]]
    else:  # closed: requests sent inside the window
        measured = [r for r in load_gen.records
                    if r["measured"] and t0 <= r["sent"] <= t0 + seconds]
    failed = [r for r in measured if not r["ok"]]
    end_to_end: dict = {"setup_s": t0 - t_start}
    ttft_p85 = None
    if traffic["loop"] == "open":
        ttft, gaps = latencies(measured, (seconds + DRAIN_TIMEOUT_S) * 1e3)
        ttft_p85 = stats.percentile(ttft, 85)
        end_to_end["itl_p95_ms"] = stats.percentile(gaps, 95)
        print(f"[serve] open loop: {len(measured)} requests, "
              f"ttft p50={stats.percentile(ttft, 50):.1f} "
              f"p85={ttft_p85:.1f} ms, {len(gaps)} gaps "
              f"p50={stats.percentile(gaps, 50):.3f} "
              f"p95={end_to_end['itl_p95_ms']:.3f} "
              f"p99={stats.percentile(gaps, 99):.3f} ms (engine p95 "
              f"{stalled['engine_itl_p95_ms']}), generator at most "
              f"{late * 1e3:.2f} ms late, in flight at the end "
              f"{backlog_end}", flush=True)
    times, amounts = credited_events(load_gen.records, t0, t0 + seconds)
    tokens_per_s = stats.slope(times, amounts)
    if traffic["loop"] == "closed":
        end_to_end["serve_tokens_per_s"] = tokens_per_s
    print(f"[serve] {len(times)} token events in the window, "
          f"{sum(amounts)} tokens credited, slope {tokens_per_s:.2f} "
          f"tokens/s, count/window {sum(amounts) / seconds:.2f}; "
          f"{len(measured)} requests, {len(failed)} failed, {stuck} stuck; "
          f"log-prob |diff| max {worst:.5f} "
          f"(tol {config['logprob_tolerance']})", flush=True)
    for r in failed[:5]:
        print(f"[serve] failed request: {r['error']}", flush=True)
    drained = stats_now["running"] == 0 and stats_now["waiting"] == 0
    if not drained:
        print(f"[serve] engine not drained: {stats_now}", flush=True)
    device = {"platform": stats_now["platform"],
              "kind": stats_now["device_kind"],
              "count": stats_now["device_count"],
              "memory_peak_bytes": memory_peak}
    # every number `correct` compares, beside its limit (run.py prints them
    # last on standard error and puts them last in the result's line)
    compared = {
        "logprob_gap_max_nats": {"value": worst if worst == worst else None,
                                 "limit": config["logprob_tolerance"]},
        "failed_requests": {"value": len(failed), "limit": 0},
        "stuck_requests": {"value": stuck, "limit": 0},
        "left_in_engine": {"value": stats_now["running"]
                           + stats_now["waiting"], "limit": 0},
        "chips": {"value": device["count"], "limit": cell["chips"]}}
    return {
        "correct": bool(ok_ref and not failed and not stuck and drained
                        and device["count"] == cell["chips"]),
        "compared": compared,
        "attempted": len(measured),
        "failed": len(failed),
        "end_to_end": end_to_end,
        "device": device,
        "observed": {
            **observed,  # before, after; polls: the traced run's watcher
            "stall": stalled,
            "events": events, "ready_s": ready_s,
            "tokens_per_s": tokens_per_s, "backlog_end": backlog_end,
            "ttft_p85_ms": ttft_p85,
            "device_kind": device["kind"], "config": config,
            "traffic": traffic,
        },
    }
