"""On the chip, by hand: a decode step's read of a latent kind that is read
whole (xing4: one pool of 640-lane rows, 32 heads on each, the first 512
lanes the values), alone: `context_attention.attend_latent` with the
Pallas kernel (`ray_tpu/ops/paged_attention.py`) against the XLA tile
loops (its predicate patched false), on one layer of the
xing4.0-29b-a4b cell's pool (49,152 pages of 16 slots, 1 GB) under
permuted block tables, at the cell's own lanes: the 32 stratified prompt
lengths of `benchmark/traffic/long-doc-sat.json` (2,790-24,049 slots,
mean 9,230), and the 29 longest of them beside 3 padded lanes (what a
saturated step decodes: `sched_decode_lanes_pct` 90.6). PERF.md section 6,
PR 52.

    chiprun -- python benchmark/selftest/chip_latent_read.py [step bytes ...]

`[read]` lines: ms a read and GB/s of the lanes' valid rows at 1,280 B a
slot, the worst difference between the two paths. Each further argument
is a `paged_attention.STEP_BYTES` to run the kernel at as well (tuning
only). The numbers also go to chiprun_out/latent_read.json."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import traffic_gen  # noqa: E402
from ray_tpu.ops import context_attention as ca  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.serve.llm.cache import KVKind, KVLayout  # noqa: E402
from ray_tpu.serve.llm.runner import ModelRunner  # noqa: E402

KERNEL = ca.reads_by_kernel
HEADS, ROW, VALUES, PAGE, PAGES, MAX_LEN = 32, 640, 512, 16, 49152, 33280
SCALE = 0.1147  # xing4's mscale^2 / sqrt(192)
READS = 6  # a decode program's layers, one after the other
OUT = {}


def timed(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def cell_lengths():
    with open("benchmark/traffic/long-doc-sat.json") as f:
        traffic = json.load(f)
    rng = np.random.default_rng(traffic["base_seed"])
    return np.sort(traffic_gen.draw_lengths(
        traffic["prompt_len"], traffic["cycle_requests"], rng))[::-1]


def read_alone(name, lens, pool, paths):
    """`READS` reads of the one layer, one after the other: ms a read and
    GB/s of the valid rows, a path; lanes longest first, as the runner
    orders a decode step's, in its groups."""
    B = len(lens)
    lay = KVLayout.of(KVKind("latent", 1, 1, ROW, 0), PAGES, PAGE)
    per = -(-MAX_LEN // PAGE)
    key = jax.random.split(jax.random.PRNGKey(1), 2)
    dt = jnp.bfloat16
    q = jax.random.normal(key[0], (B, 1, HEADS, ROW), dt)
    own = jax.random.normal(key[1], (B, 1, ROW), dt)
    rng = np.random.default_rng(0)
    tables = np.zeros((B, per), np.int32)
    free = 1 + rng.permutation(PAGES - 1)
    at = 0
    for b, n in enumerate(lens):
        need = -(-int(n) // PAGE)
        tables[b, :need] = free[at:at + need]
        at += need
    tables, lengths = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    own_valid = jnp.ones((B, 1, 1), bool)
    group = ModelRunner.lanes_per_group(B)
    res, outs = {}, {}
    for path, step_bytes in paths:
        ca.reads_by_kernel = KERNEL if path == "kernel" else \
            (lambda *a, **k: False)
        if step_bytes:
            pa.STEP_BYTES = step_bytes
            jax.clear_caches()  # `attend_latent` jits the kernel's call

        @jax.jit
        def f(q, own, pool, v_pool, tables, lengths):
            ctx = ca.CachedContext.of(lay, pool, v_pool, tables, lengths,
                                      group)

            def body(c, i):
                o = ca.attend_latent(q, own, own_valid, ctx, i * 0, dt,
                                     values=VALUES, scale=SCALE)
                return c + o.astype(jnp.float32), None
            return jax.lax.scan(body, jnp.zeros(
                (B, 1, HEADS, VALUES), jnp.float32), jnp.arange(READS))[0]

        args = (q, own, pool, jnp.zeros(lay.v_shape, dt), tables, lengths)
        s = timed(f, *args)
        label = path if not step_bytes else f"{path}@{step_bytes}"
        outs[label] = np.asarray(f(*args))
        byts = float(np.sum(lens)) * ROW * 2 * READS
        res[label] = {"ms_a_read": s / READS * 1e3,
                      "GBps": byts / s / 1e9}
        if path == "kernel":
            res[label]["pages_a_step"] = pa.pages_a_step(lay, 2, per)
    ca.reads_by_kernel = KERNEL
    first = next(iter(outs))
    res["max_abs_diff"] = max(float(np.max(np.abs(o - outs[first])))
                              for o in outs.values())
    res["max_abs"] = float(np.max(np.abs(outs[first])))
    print(f"[read] {name}: lanes {B}, slots {int(np.sum(lens))}, longest "
          f"{int(np.max(lens))}: " + json.dumps(res), flush=True)
    OUT[name] = res


def main():
    print("device", jax.devices()[0].device_kind, flush=True)
    default = pa.STEP_BYTES
    paths = [("kernel", 0), ("loop", 0)] + [
        ("kernel", int(a)) for a in sys.argv[1:]]
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (1, PAGES, PAGE, ROW), jnp.bfloat16)
    lens = cell_lengths()
    read_alone("32 lanes, the cell's prompts", lens, pool, paths)
    pa.STEP_BYTES = default
    jax.clear_caches()
    read_alone("29 lanes of 32, 3 padded",
               np.concatenate([lens[:29], [0, 0, 0]]), pool, paths[:2])
    read_alone("32 lanes of 2,790", np.full(32, 2790), pool, paths[:2])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/latent_read.json", "w") as f:
        json.dump(OUT, f, indent=1)


if __name__ == "__main__":
    main()
