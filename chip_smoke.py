#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on a TPU.

Drives the two hot paths once, through the entry points a user calls, at
gpt2-small's published width (12 x 768, 12 heads of 64, vocab 50257 padded
to 50304, context 1024, bf16; seeded random weights):

- train:  JaxTrainer.fit() -> one worker owning every chip of the host ->
          build_mesh(data=-1), init_sharded_state, make_train_step, a few
          adamw steps at batch 8 per chip x 1024 on one seeded batch;
- chips:  (hosts with more than one chip) two concurrent num_tpus=1 actors
          each see one chip, and not the same one;
- serve:  serve.run(build_llm_app(model="gpt2", preset="small")) with the
          KV pool sized from device memory; greedy requests through the
          handle stream and the HTTP proxy; the decode steps must have
          read their context with the Pallas kernel
          (ops/paged_attention.py), which the code picks on a TPU.

This process never initialises a JAX backend: a chip belongs to one process
at a time, and each phase runs in a worker process the runtime spawns for it
and ends before the next needs the chip. What this process knows about the
device it asks the workers for.

Exit code 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}
only if every phase passed on a TPU. Anything else — no chip, no ray_tpu
beside this file, a failed check, a hang — ends non-zero with the reason on
the last line. Numbers printed here are set-up facts, not metrics.
"""

from __future__ import annotations

import json
import math
import os
import signal
import sys
import threading
import time

TRAIN_STEPS = 5
BATCH_PER_CHIP, SEQ = 8, 1024
# flash kernel vs the f32 einsum reference at (2, 1024, 12, 64) bf16: max
# abs error over max |reference|. bf16 keeps 8 mantissa bits (2^-8 = 0.4%
# per rounding); 2% leaves room for the handful of roundings in a row.
FLASH_TOL = 2e-2
# loss of the data=n run vs the same global batch on one chip, per step
LOSS_TOL = 1e-2
MAX_TOKENS = 16
DEADLINE_S = 1100  # the contract allows 1200 s, compilation included


class SmokeFailure(Exception):
    pass


def check(cond, reason: str):
    if not cond:
        raise SmokeFailure(reason)


def say(phase: str, **facts):
    print(f"[chip_smoke] phase={phase} "
          + " ".join(f"{k}={json.dumps(v)}" for k, v in facts.items()),
          flush=True)


# ------------------------------------------------------------------ train


def train_loop(config):
    """Runs in the train worker (the process that owns the chips)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.gpt2 import (
        GPT2Config,
        gpt2_loss,
        gpt2_partition_rules,
        init_gpt2,
    )
    from ray_tpu.ops.attention import causal_attention_reference
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.ops import collective_op_counts
    from ray_tpu.train.spmd import (
        batch_shardings,
        init_sharded_state,
        make_train_step,
    )
    from ray_tpu.util.metrics import prometheus_text

    t_start = time.monotonic()
    devices = jax.devices()
    n = len(devices)
    facts = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": n,
        "local_device_count": len(jax.local_devices()),
        "pid": os.getpid(),
        "jax": jax.__version__,
        "libtpu": _libtpu_version(),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "TPU_VISIBLE_CHIPS": os.environ.get("TPU_VISIBLE_CHIPS"),
    }
    cfg = GPT2Config.small()
    rules = gpt2_partition_rules()
    tx = optax.adamw(3e-4, weight_decay=0.1)

    def init():
        return init_gpt2(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return gpt2_loss(p, b, cfg)

    B = config["batch_per_chip"] * n
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, config["seq"] + 1), 0, cfg.vocab_size,
        jnp.int32))
    host_batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    mesh = build_mesh(MeshSpec(data=-1), devices=devices)
    state = init_sharded_state(init, tx, mesh, rules)
    batch = jax.device_put(host_batch, batch_shardings(mesh, host_batch))
    step = make_train_step(loss_fn, tx)
    # abstract arguments, taken before the step donates the state
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding),
        (state, batch))
    losses = []
    with jax.set_mesh(mesh):
        for i in range(config["steps"]):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
            train.report({"step": i, "loss": losses[-1],
                          **(facts if i == 0 else {})})
        # the program that just ran, as text: is the flash kernel in it,
        # and (n > 1) does it reduce across chips
        hlo = step.jitted.lower(*abstract).compile().as_text()
    final = {
        "flash_custom_calls": hlo.count("tpu_custom_call"),
        "collectives": collective_op_counts(hlo),
        "train_compile_s": _metric_sum(prometheus_text(),
                                       "train_compile_seconds_sum"),
    }

    if n > 1:
        # really spread: every parameter and optimizer leaf has a shard
        # on each chip, and the chips hold memory of the same order
        leaves = jax.tree.leaves((state.params, state.opt_state))
        final["leaf_device_counts"] = sorted(
            {len({s.device.id for s in leaf.addressable_shards})
             for leaf in leaves})
        final["bytes_in_use"] = [
            d.memory_stats()["bytes_in_use"] for d in devices]
        # the same global batch on ONE chip: n microbatches accumulated
        # into one update, from the same seed
        mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
        state1 = init_sharded_state(init, tx, mesh1, rules, accum_steps=n)
        step1 = make_train_step(loss_fn, tx, accum_steps=n)
        per = config["batch_per_chip"]
        micro = [jax.device_put(
            {k: v[i * per:(i + 1) * per] for k, v in host_batch.items()},
            batch_shardings(mesh1, host_batch)) for i in range(n)]
        one_chip = []
        with jax.set_mesh(mesh1):
            for _ in range(config["steps"]):
                ls = []
                for mb in micro:
                    state1, m1 = step1(state1, mb)
                    ls.append(float(m1["loss"]))
                one_chip.append(sum(ls) / n)
        final["one_chip_losses"] = one_chip

    # flash forward and gradients against the reference, on this chip
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, g = (jax.random.normal(kk, (2, 1024, 12, 64), jnp.float32)
                  .astype(jnp.bfloat16) for kk in ks)

    def scalar(attn, dtype):
        def f(q, k, v):
            out = attn(q.astype(dtype), k.astype(dtype), v.astype(dtype))
            return jnp.sum(out.astype(jnp.float32)
                           * g.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, ref_o), ref_g = scalar(causal_attention_reference,
                                   jnp.float32)(q, k, v)
    (_, got_o), got_g = scalar(flash_attention, jnp.bfloat16)(q, k, v)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / np.abs(b).max())

    final["flash_rel_err"] = {
        "out": rel(got_o, ref_o), "dq": rel(got_g[0], ref_g[0]),
        "dk": rel(got_g[1], ref_g[1]), "dv": rel(got_g[2], ref_g[2])}
    final["worker_wall_s"] = round(time.monotonic() - t_start, 1)
    train.report({"step": config["steps"], "final": final})


def _libtpu_version() -> str | None:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


def _metric_sum(prom_text: str, name: str) -> float:
    """Sum of every sample of `name` on a Prometheus page."""
    total = 0.0
    for line in prom_text.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return round(total, 2)


def phase_train(n_chips: int, workdir: str) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.monotonic()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"steps": TRAIN_STEPS, "seq": SEQ,
                           "batch_per_chip": BATCH_PER_CHIP},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"CPU": 1.0, "TPU": float(n_chips)}),
        run_config=RunConfig(name="chip_smoke", storage_path=workdir),
    ).fit()
    wall = time.monotonic() - t0
    reports = result.metrics_history
    check(len(reports) == TRAIN_STEPS + 1,
          f"train: {len(reports)} reports, expected {TRAIN_STEPS + 1}")
    facts, final = reports[0], reports[-1]["final"]
    losses = [r["loss"] for r in reports[:-1]]
    device = {"platform": facts["platform"], "kind": facts["device_kind"],
              "count": facts["device_count"]}
    say("train", **device, wall_s=round(wall, 1),
        compile_s=final["train_compile_s"], cache_dir=facts["cache_dir"],
        worker_pid=facts["pid"], jax=facts["jax"], libtpu=facts["libtpu"],
        losses=[round(x, 4) for x in losses],
        flash_custom_calls=final["flash_custom_calls"],
        flash_rel_err=final["flash_rel_err"],
        collectives=final["collectives"])
    check(facts["platform"] == "tpu",
          f"train worker ran on {facts['platform']!r}, not tpu")
    check(facts["pid"] != os.getpid(), "train ran in the driver process")
    check(facts["device_count"] == n_chips,
          f"train worker saw {facts['device_count']} chips of {n_chips}")
    check(all(math.isfinite(x) for x in losses),
          f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"train: loss did not fall: {losses}")
    check(final["flash_custom_calls"] >= 3,
          f"train: {final['flash_custom_calls']} pallas custom calls in the "
          f"compiled step — the flash kernel (fwd, dq, dkv) is not in it")
    worst = max(final["flash_rel_err"].values())
    check(worst <= FLASH_TOL,
          f"flash vs reference: {final['flash_rel_err']} > {FLASH_TOL}")
    if n_chips > 1:
        say("train-spread", leaf_device_counts=final["leaf_device_counts"],
            bytes_in_use=final["bytes_in_use"],
            one_chip_losses=[round(x, 4) for x in final["one_chip_losses"]])
        check(final["leaf_device_counts"] == [n_chips],
              f"state not on every chip: {final['leaf_device_counts']}")
        b = final["bytes_in_use"]
        check(max(b) <= 2 * min(b), f"chips unevenly filled: {b}")
        check(final["collectives"].get("allreduce", 0) > 0,
              f"no all-reduce in the data={n_chips} step")
        for a, o in zip(losses, final["one_chip_losses"]):
            check(abs(a - o) <= LOSS_TOL * abs(o),
                  f"data={n_chips} losses {losses} vs one chip "
                  f"{final['one_chip_losses']}")
    return device


# ------------------------------------------------------------------ chips


def phase_chips():
    """k-of-n visibility: two concurrent one-chip actors."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=1, num_cpus=0)
    class OneChip:
        def look(self):
            import jax
            import jax.numpy as jnp

            x = jnp.ones((512, 512), jnp.bfloat16)
            return {"visible": os.environ.get("TPU_VISIBLE_CHIPS"),
                    "platform": jax.devices()[0].platform,
                    "local_devices": len(jax.local_devices()),
                    "pid": os.getpid(),
                    "trace": float((x @ x).astype(jnp.float32)[0, 0])}

    t0 = time.monotonic()
    actors = [OneChip.remote(), OneChip.remote()]
    try:
        # both hold their chip at once: look() runs in each concurrently
        seen = ray_tpu.get([a.look.remote() for a in actors], timeout=240)
    finally:
        for a in actors:
            ray_tpu.kill(a)
    say("chips", wall_s=round(time.monotonic() - t0, 1), actors=seen)
    for s in seen:
        check(s["platform"] == "tpu" and s["local_devices"] == 1
              and s["trace"] == 512.0,
              f"one-chip actor saw {s}")
    check(seen[0]["visible"] != seen[1]["visible"]
          and None not in (seen[0]["visible"], seen[1]["visible"]),
          f"two one-chip actors were given the same chip: {seen}")


# ------------------------------------------------------------------ serve


def _prompts() -> dict[str, list[int]]:
    import numpy as np

    rng = np.random.RandomState(0)

    def p(n):
        return rng.randint(1, 50257, size=n).tolist()

    return {"short": p(12), "long": p(300),  # long crosses the 256 chunk
            "burst0": p(24), "burst1": p(31), "burst2": p(40),
            "burst3": p(57)}


def _stream(handle, prompt) -> tuple[list[int], list[float]]:
    """One greedy request through the handle: (tokens, their log-probs)."""
    import ray_tpu

    gen = handle.options(stream=True).remote(
        {"prompt": prompt, "max_tokens": MAX_TOKENS, "logprobs": True})
    events = [ray_tpu.get(r, timeout=120) for r in gen]
    *tokens, final = events
    check(final.get("done") and final.get("finish_reason") == "length",
          f"stream ended with {final}")
    ids = [e["token"] for e in tokens]
    check(ids == final["token_ids"] and len(ids) == MAX_TOKENS,
          f"streamed {ids} but final event says {final['token_ids']}")
    return ids, [e["logprob"] for e in tokens]


def _http_stream(addr: str, app: str, prompt) -> list[int]:
    import urllib.request

    req = urllib.request.Request(
        f"http://{addr}/{app}?stream=1",
        data=json.dumps({"prompt": prompt,
                         "max_tokens": MAX_TOKENS}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [json.loads(line)["result"]
                  for line in resp.read().splitlines() if line.strip()]
    check(events and events[-1].get("done"), f"http stream: {events[-1:]}")
    return [e["token"] for e in events[:-1]]


def phase_serve() -> dict:
    """Deploy, ask, check, delete. Returns {prompt name: (tokens,
    logprobs)}."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app
    from ray_tpu.util import state

    app = "llm"
    # at the default pool (0.3 of memory): the pool is read and written
    # in place (cache.KVLayout), so no program needs room for a copy of
    # it (PERF.md, findings of PR 26)
    t0 = time.monotonic()
    handle = serve.run(build_llm_app(model="gpt2", preset="small"),
                       name=app)
    ready_s = time.monotonic() - t0
    try:
        addr = serve.start_proxy(port=0)
        prompts = _prompts()
        out = {"short": _stream(handle, prompts["short"]),
               "long": _stream(handle, prompts["long"])}
        check(_stream(handle, prompts["short"])[0] == out["short"][0],
              "the same greedy prompt gave different tokens")
        burst = [k for k in prompts if k.startswith("burst")]
        errors: list = []

        def ask(name):
            try:
                out[name] = _stream(handle, prompts[name])
            except BaseException as e:  # re-raised below, in the driver
                errors.append(e)

        threads = [threading.Thread(target=ask, args=(k,)) for k in burst]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        if errors:
            raise errors[0]
        check(all(k in out for k in burst), "a burst request never ended")
        check(_http_stream(addr, app, prompts["short"]) == out["short"][0],
              "HTTP ?stream=1 and the handle stream disagree")
        stats = state.llm_status(app)
        compile_s = _metric_sum(state.cluster_metrics(),
                                "serve_llm_compile_seconds_sum")
    finally:
        serve.delete(app)
    check(len(stats) == 1, f"{len(stats)} replicas")
    st = stats[0]
    decode = st["context_by_kind"]["full"]["decode"]
    say("serve", platform=st["platform"],
        kind=st["device_kind"], count=st["device_count"],
        wall_s=round(time.monotonic() - t0, 1),
        replica_ready_s=round(ready_s, 1), compile_s=compile_s,
        compiled_programs=st["compiled_programs"],
        blocks_total=st.get("blocks_total"),
        kernel_steps=decode["kernel_steps"],
        tokens={k: v[0][:6] for k, v in sorted(out.items())})
    check(st["platform"] == "tpu",
          f"replica ran on {st['platform']!r}, not tpu")
    check(st["running"] == 0 and st["blocks_used"] == 0,
          f"engine not drained: running={st['running']} "
          f"blocks_used={st['blocks_used']}")
    # the kernel reads whole pages to each lane's own length: under a
    # page (16 slots) over what is valid, a lane (at most 8) and step
    over = decode["slots_read"] - decode["slots_valid"]
    steps = decode["kernel_steps"]
    check(steps > 0 and over < 16 * 8 * steps,
          f"decode steps did not read their context with the kernel, to "
          f"the lanes' own lengths: {decode}")
    return out


# ------------------------------------------------------------------- main


def run() -> dict:
    try:
        import ray_tpu
        from ray_tpu import _native, accelerators, serve
    except ImportError as e:
        raise SmokeFailure(f"ray_tpu is not importable from "
                           f"{os.getcwd()}: {e}") from e
    n_chips = accelerators.TPUAcceleratorManager \
        .get_current_node_num_accelerators()
    check(n_chips > 0, "no TPU chip on this host: no /dev/accel* and no "
                       "/dev/vfio/<n> (JAX_PLATFORMS="
                       f"{os.environ.get('JAX_PLATFORMS')!r})")
    for lib in ("object_store", "channel", "lineio"):
        check(_native.build_library(lib),
              f"native library {lib} did not build (no g++?)")
    import tempfile

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.monotonic()
    ray_tpu.init()
    try:
        check(ray_tpu.cluster_resources().get("TPU") == n_chips,
              f"runtime sees TPU={ray_tpu.cluster_resources().get('TPU')}, "
              f"device tree has {n_chips}")
        device = phase_train(n_chips, workdir)
        if n_chips > 1:
            phase_chips()
        phase_serve()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "the driver process initialised a JAX backend")
    say("done", wall_s=round(time.monotonic() - t0, 1),
        cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    return device


def main() -> int:
    def on_deadline(signum, frame):
        raise SmokeFailure(f"not finished after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        device = run()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", flush=True)
        return 1
    except Exception as e:  # any phase's own error: report it, exit non-zero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: "
              f"{str(e).strip().splitlines()[-1] if str(e).strip() else ''}",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
