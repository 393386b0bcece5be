"""Runs any train cell from (configuration file, traffic file).

The parent process stays off JAX: `JaxTrainer.fit()` places one worker that
owns every chip the cell asks for, and `train_loop` below runs there. It
builds the mesh, state and step as a user of `ray_tpu.train` does
(`build_mesh`, `state_shardings`, `make_train_step`), warms up, measures,
and then checks the first step's loss against the plain reference.

`train_tokens_per_s_chip`: tokens of the optimizer steps that ended
(`float(loss)`, which waits for the device) inside the window, over the
window's length and the chip count. The window runs from the start of the
first measured step to the end of the first step that ends at or after
`--seconds`, so it holds whole steps only: all the work, all the time.
"""

from __future__ import annotations

import shutil
import tempfile
import time

from benchmark.device import memory_peak, profiler_options
from benchmark.model_api import load, sizes


def train_loop(config: dict):
    """In the train worker, the process that owns the chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.spmd import (
        TrainState,
        batch_shardings,
        make_train_step,
        state_shardings,
    )
    from ray_tpu.util.tracing import jit_cache_size

    devices = jax.devices()
    n = len(devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": n}
    marks = {"ready": time.monotonic()}
    train.report({"event": "ready", "t": marks["ready"], "device": device})
    if device["platform"] != config["platform"]:
        raise RuntimeError(f"train worker is on {device['platform']!r}, "
                           f"the cell needs {config['platform']!r}")

    model, traffic = config["model"], config["traffic"]
    cfg = load(model["config"])()
    init, loss = load(model["init"]), load(model["loss"])
    rules = load(model["rules"])()
    opt = dict(config["optimizer"])
    tx = getattr(optax, opt.pop("name"))(**opt)
    seed = config["weight_seed"]
    seq, per_chip = traffic["seq"], traffic["batch_per_chip"]
    B, nb = per_chip * n, traffic["pool_batches"]

    mesh = build_mesh(MeshSpec(**traffic["mesh"]), devices=devices)
    # The state, made on its devices in one jitted call. This is the body of
    # `init_sharded_state` with the PRNG key as an argument: that function
    # takes an init without arguments, which bakes the seed into the program
    # as a constant, so every new seed would compile it anew (40-50 s on the
    # chip; my chip runs, PR 24) instead of finding it in the cache.
    def make_state(key):
        return TrainState.create(init(key, cfg), tx)

    key = jax.random.PRNGKey(seed)
    with jax.set_mesh(mesh):
        state = jax.jit(make_state, out_shardings=state_shardings(
            rules, jax.eval_shape(make_state, key), mesh))(key)
    # the data: a pool of device-resident batches made in one jitted call
    example = {"tokens": np.zeros((B, seq), np.int32),
               "targets": np.zeros((B, seq), np.int32)}
    shardings = batch_shardings(mesh, example)

    def make_pool(key):
        toks = jax.random.randint(key, (nb, B, seq + 1), 0, cfg.vocab_size,
                                  jnp.int32)
        return [{"tokens": toks[i, :, :-1], "targets": toks[i, :, 1:]}
                for i in range(nb)]

    pool = jax.jit(make_pool, out_shardings=[shardings] * nb)(
        jax.random.PRNGKey(seed + 1))
    order = np.random.default_rng(config["seed"]).integers(0, nb, size=1 << 16)
    step = make_train_step(lambda p, b: loss(p, b, cfg), tx)
    tokens_per_step = B * seq
    facts: dict = {"event": "final", "device": device,
                   "tokens_per_step": tokens_per_step}

    with jax.set_mesh(mesh):
        # warm-up: the first step compiles; its loss is the one checked
        state, m = step(state, pool[0])
        first_loss = float(m["loss"])
        marks["first_step"] = time.monotonic()
        for i in range(traffic["warmup_steps"] - 1):
            state, m = step(state, pool[order[i] % nb])
            float(m["loss"])
        k = traffic["warmup_steps"]

        if config["trace_dir"]:
            jax.profiler.start_trace(config["trace_dir"],
                                     profiler_options=profiler_options())
            for _ in range(traffic["trace_steps"]):
                state, m = step(state, pool[order[k] % nb])
                float(m["loss"])
                k += 1
            jax.profiler.stop_trace()
            facts["traced_steps"] = traffic["trace_steps"]

        compiled_before = jit_cache_size(step.jitted)
        ends = []
        t0 = time.monotonic()
        while True:
            state, m = step(state, pool[order[k % len(order)] % nb])
            value = float(m["loss"])  # waits for the step, as a user does
            now = time.monotonic()
            ends.append(now)
            train.report({"step": len(ends), "loss": value})
            k += 1
            if now - t0 >= config["seconds"]:
                break
        facts.update(
            marks=marks, t0=t0, window_s=ends[-1] - t0, steps=len(ends), last_loss=value,
            compiles_in_window=jit_cache_size(step.jitted) - compiled_before)

    facts["memory_peak_bytes"] = max(
        memory_peak(d.memory_stats()) for d in devices)

    # correctness, outside the window: the first step's loss against the
    # plain float32 reference with the same seeded weights and batch
    leaves = jax.tree.leaves((state.params, state.opt_state))
    shards = pool[0]["tokens"].addressable_shards
    facts["spread"] = {
        "leaf_device_counts": sorted(
            {len({s.device.id for s in leaf.addressable_shards})
             for leaf in leaves}),
        "batch_shard_shapes": sorted({tuple(s.data.shape) for s in shards}),
        "batch_shard_devices": len({s.device.id for s in shards}),
    }
    del state
    one = devices[0]
    params0 = jax.jit(lambda k: init(k, cfg),
                      out_shardings=jax.sharding.SingleDeviceSharding(one))(
        key)
    batch0 = jax.device_put(pool[0], one)
    facts["first_loss"] = first_loss
    facts["reference_loss"] = load(model["reference_loss"])(
        params0, batch0["tokens"], batch0["targets"], config["sizes"])
    train.report(facts)


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float,
        platform: str = "tpu") -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    chips = cell["chips"]
    workdir = tempfile.mkdtemp(prefix="bench_train_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    resources = {"CPU": 1.0}
    if platform == "tpu":
        resources["TPU"] = float(chips)
    try:
        t_fit = time.monotonic()
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "model": config["model"], "sizes": sizes(config),
                "optimizer": config["trainer"]["optimizer"],
                "traffic": traffic, "seed": seed,
                "weight_seed": seed % (2 ** 31 - 1), "seconds": seconds,
                "trace_dir": trace_dir, "platform": platform},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=platform == "tpu",
                resources_per_worker=resources,
                num_cpu_devices_per_worker=(
                    chips if platform == "cpu" else None)),
            run_config=RunConfig(name=cell["name"], storage_path=workdir),
        ).fit()
        reports = result.metrics_history
        ready, final = reports[0], reports[-1]
        if final.get("event") != "final":
            raise RuntimeError(f"train worker ended early: {final}")
        events = None
        if trace:
            from benchmark import trace_reduce

            events = trace_reduce.load(trace_dir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    n = final["device"]["count"]
    tokens = final["steps"] * final["tokens_per_step"]
    rate_chip = tokens / final["window_s"] / n
    tol = config["trainer"]["loss_tolerance"]
    diff = abs(final["first_loss"] - final["reference_loss"])
    spread = final["spread"]
    per_chip = (traffic["batch_per_chip"], traffic["seq"])
    checks = {
        "first-step loss within tolerance of the float32 reference":
            diff <= tol,
        "nothing compiled inside the window":
            final["compiles_in_window"] == 0,
        "every state leaf on every chip":
            spread["leaf_device_counts"] == [n],
        "equal shards of one global batch, one per chip":
            spread["batch_shard_shapes"] == [per_chip]
            and spread["batch_shard_devices"] == n,
        "worker saw the chips the cell asks for": n == chips,
    }
    print(f"[train] steps={final['steps']} window_s={final['window_s']:.3f} "
          f"tokens/s/chip={rate_chip:.1f} first_loss={final['first_loss']:.5f}"
          f" reference={final['reference_loss']:.5f} |diff|={diff:.5f} "
          f"tol={tol} last_loss={final['last_loss']:.4f} spread={spread} "
          f"set-up: fit->worker {ready['t'] - t_fit:.1f} s, state+data+first "
          f"step {final['marks']['first_step'] - final['marks']['ready']:.1f}"
          f" s, to the window {final['t0'] - final['marks']['first_step']:.1f}"
          f" s", flush=True)
    for name, ok in checks.items():
        if not ok:
            print(f"[train] CHECK FAILED: {name}", flush=True)
    return {
        "correct": all(checks.values()),
        # every number `correct` compares, beside its limit
        "compared": {
            "first_loss_diff": {"value": diff, "limit": tol},
            "compiles_in_window": {"value": final["compiles_in_window"],
                                   "limit": 0},
            "chips": {"value": n, "limit": chips}},
        "attempted": final["steps"],
        "failed": 0,
        "end_to_end": {
            "train_tokens_per_s_chip": rate_chip,
            "setup_s": final["t0"] - t_start,
        },
        "device": {**final["device"],
                   "memory_peak_bytes": final["memory_peak_bytes"]},
        # what the per-layer readers see
        "observed": {
            "events": events,
            "ready_s": ready["t"] - t_fit,
            "tokens_per_s_chip": rate_chip,
            "traced_steps": final.get("traced_steps"),
            "device_kind": final["device"]["kind"],
            "config": config, "traffic": traffic, "sizes": sizes(config),
        },
    }
