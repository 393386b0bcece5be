"""On the chip, by hand: the cached-context read alone (a scan of the read
over a pool's layers, nothing else), the Pallas kernel
(`ray_tpu/ops/paged_attention.py`) against the XLA tile loops
(`context_attention.attend_cached` with its predicate patched false), and
the whole decode programs of gpt2-large and the OLMoE cut through
`ModelRunner` on both paths, in one process (PERF.md section 5, PR 41).

    chiprun -- python benchmark/selftest/chip_ctx_read.py [read] ...

`[read]` lines: ms a layer and GB/s of the lanes' valid K and V rows;
`[program]` lines: ms a decode step, the host's clock around 24 launches
queued back to back. STEP_BYTES=<n> overrides the kernel's bytes a step
(tuning only). The numbers also go to chiprun_out/ctx_read_<tag>.json."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import context_attention as ca  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.serve.llm.cache import KVKind, KVLayout  # noqa: E402

KERNEL = ca.reads_by_kernel
OUT = {}


def timed(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def read_alone(name, HK, R, D, L, lens, n_pages=64, bs=16, T=1):
    """A scan of the read over L layers: ms a layer and GB/s of the valid
    K and V rows, for the kernel and for the loops."""
    B = len(lens)
    lay = KVLayout.of(KVKind("full", L, HK, D, D), 1 + B * n_pages, bs)
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    dt = jnp.bfloat16
    kp = jax.random.normal(key[0], lay.shape, dt)
    vp = jax.random.normal(key[1], lay.v_shape, dt)
    q = jax.random.normal(key[2], (B, T, HK, R, D), dt)
    k = jax.random.normal(key[3], (B, T, HK, D), dt)
    v = jax.random.normal(key[4], (B, T, HK, D), dt)
    order = np.argsort(-np.asarray(lens), kind="stable")
    lens = np.asarray(lens)[order]
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        B * n_pages).reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    own = ca.causal_rows(jnp.ones((B, T), bool))
    group = 1 if B < 8 else max(2, B // 8)
    res = {}
    outs = {}
    for path in ("kernel", "loop"):
        ca.reads_by_kernel = KERNEL if path == "kernel" else \
            (lambda *a, **k: False)

        @jax.jit
        def f(q, k, v, kp, vp, tables, lengths):
            ctx = ca.CachedContext.of(lay, kp, vp, tables, lengths, group)

            def body(c, layer):
                o = ca.attend_cached(q, k, v, own, ctx, layer, dt)
                return c + o.astype(jnp.float32), None
            return jax.lax.scan(body, jnp.zeros(
                (B, T, HK, R, D), jnp.float32), jnp.arange(L))[0]

        s = timed(f, q, k, v, kp, vp, tables, lengths)
        outs[path] = np.asarray(f(q, k, v, kp, vp, tables, lengths))
        byts = float(np.sum(lens)) * (lay.row + lay.v_row) * 2 * L
        res[path] = {"ms_a_layer": s / L * 1e3, "ms": s * 1e3,
                     "GBps": byts / s / 1e9}
    ca.reads_by_kernel = KERNEL
    res["max_abs_diff"] = float(np.max(np.abs(outs["kernel"] - outs["loop"])))
    res["pages_a_step"] = pa.pages_a_step(lay, 2, n_pages)
    print(f"[read] {name} lanes {[int(n) for n in lens]}: "
          + json.dumps(res), flush=True)
    OUT["read " + name + " " + str(list(map(int, lens)))] = res


def programs(family, preset, engine, cases, verify=0):
    from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters
    ad = adapters()[family]
    cfg = ad.presets[preset]()
    params = ad.init_fn(jax.random.PRNGKey(0), cfg)
    runners = {}
    for path in ("kernel", "loop"):
        ca.reads_by_kernel = KERNEL if path == "kernel" else \
            (lambda *a, **k: False)
        r = ModelRunner(ad, cfg, params, **engine, num_draft_tokens=verify)
        per = r.max_blocks_per_seq
        for name, lens in cases:
            items = [DecodeItem(1, int(n), list(range(1 + i * per,
                                                      1 + (i + 1) * per))
                                [:int(n) // r.block_size + 1], 0.0)
                     for i, n in enumerate(lens)]
            r.decode(items)
            r.decode(items)
            t0 = time.perf_counter()
            flights = [r.launch_decode(items) for _ in range(24)]
            for fl in flights:
                r.collect(fl)
            ms = (time.perf_counter() - t0) / 24 * 1e3
            print(f"[program] {preset} {path} decode {name}: {ms:.3f} ms",
                  flush=True)
            OUT.setdefault(f"program {preset} {name}", {})[path] = ms
        if verify:
            n = min(900, r.max_model_len - 8)
            table = list(range(1, 1 + per))
            r.verify(1, n, [1] * verify, table, 0.0)
            each = []
            for _ in range(16):
                t0 = time.perf_counter()
                r.verify(1, n, [1] * verify, table, 0.0)
                each.append((time.perf_counter() - t0) * 1e3)
            ms = float(np.median(each))
            print(f"[program] {preset} {path} verify-{verify + 1} x {n} "
                  f"(blocking; median of 16, least {min(each):.3f}, most "
                  f"{max(each):.3f}): {ms:.3f} ms", flush=True)
            OUT.setdefault(f"program {preset} verify", {})[path] = ms
        del r
    ca.reads_by_kernel = KERNEL


def main():
    if os.environ.get("STEP_BYTES"):
        pa.STEP_BYTES = int(os.environ["STEP_BYTES"])
    what = sys.argv[1:] or ["read", "programs", "olmoe"]
    print("device", jax.devices()[0].device_kind, flush=True)
    if "read" in what:
        read_alone("gpt2-large", 20, 1, 64, 36, [1000] * 4)
        read_alone("gpt2-large", 20, 1, 64, 36, [750] * 4)
        read_alone("gpt2-large", 20, 1, 64, 36, [100] * 4)
        read_alone("gpt2-large", 20, 1, 64, 36, [1000] * 8)
        read_alone("gpt2-large", 20, 1, 64, 36,
                   [1000, 900, 800, 700, 600, 500, 0, 0])
        read_alone("gpt2-large", 20, 1, 64, 36, [1000])
        read_alone("gpt2-large", 20, 1, 64, 36, [96])
        read_alone("gpt2-large-verify3", 20, 1, 64, 36, [900], T=3)
        read_alone("olmoe", 16, 1, 128, 8, [1000] * 16)
        read_alone("olmoe", 16, 1, 128, 8, list(range(160, 1000, 53)))
        read_alone("nemotron", 2, 16, 128, 2, [2000] * 32, n_pages=160)
    if "programs" in what:
        eng = dict(block_size=16, num_blocks=513, max_model_len=1024,
                   max_batch_size=8, prefill_chunk_size=256)
        cases = [(f"{b} x {n}", [n] * b) for b in (1, 2, 4, 8)
                 for n in (100, 1000)]
        cases += [("4 x 750", [750] * 4), ("8 x 750", [750] * 8),
                  ("8 mixed 100-1000",
                   [1000, 870, 740, 610, 480, 350, 220, 100]),
                  ("5 x 750 (bucket 8)", [750] * 5)]
        programs("gpt2", "large", eng, cases, verify=2)
    if "olmoe" in what:
        eng = dict(block_size=16, num_blocks=1088, max_model_len=1024,
                   max_batch_size=16, prefill_chunk_size=256)
        programs("llama", "olmoe_1b_7b_l8", eng,
                 [("16 x 1000", [1000] * 16), ("16 x 160-1000",
                                                list(range(160, 1000, 53)))])
    os.makedirs("chiprun_out", exist_ok=True)
    tag = os.environ.get("STEP_BYTES", "default")
    with open(f"chiprun_out/ctx_read_{tag}.json", "w") as f:
        json.dump(OUT, f, indent=1)


if __name__ == "__main__":
    main()
