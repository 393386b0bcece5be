"""What a decode step's read of the latent rows costs on the chip, one
layer of the glm-5 cell's latent kind at its real sizes, by hand:

    chiprun -- python3 benchmark/selftest/chip_decode_read_glm_5.py

Times `ops/context_attention.py` `attend_selected` for one row a lane
(index pass, exact top-k, attention core) at 16 and at 32 lanes, in groups
as the runner makes them (`lanes_per_group`), the lanes ordered longest
first, over a pool of the cell's 24,576 pages, for two sets of lengths:
`cell`, drawn as the cell's traffic draws them (prompts lognormal median
4,096 sigma 0.7 clipped 1,024-16,384, a uniform part of 128-384 output
tokens behind them), and `full`, every lane at 16,384. Beside it the same
step's index pass and top-k alone (`select`), so that the core is the
difference. Prints one JSON line and writes
chiprun_out/decode_read_glm_5.json. PERF.md section 6 (PR 40, finding 8)
has the readings that chose the decode step's form."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_glm_5 as reference
    from benchmark.parity_mimo_v2 import program_config
    from ray_tpu.ops import context_attention as ca
    from ray_tpu.serve.llm.cache import KVKind, KVLayout
    from ray_tpu.serve.llm.runner import ModelRunner

    with open(reference._CONFIG) as f:
        config = json.load(f)
    cfg = program_config(config)
    engine = config["engine"]
    page, longest = engine["block_size"], engine["max_model_len"]
    # every layer's pool, as served: the tile is sized by the layers
    layout = KVLayout.of(KVKind(*cfg.kv_kinds()[0]), engine["num_blocks"],
                         page)
    layer = layout.kv_layers // 2
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    latent_pool, index_pool = (
        jax.random.normal(k, shape, cfg.dtype)
        for k, shape in zip(jax.random.split(key), (
            layout.shape, layout.v_shape)))
    H, row = cfg.num_attention_heads, layout.row
    values, scale = cfg.kv_lora_rank, cfg.qk_head_dim ** -0.5

    def step(core: bool):
        def run(latent_pool, index_pool, tables, lengths, q, own, qi, ki, w,
                group):
            ctx = ca.CachedContext.of(layout, latent_pool, index_pool,
                                      tables, lengths, group)
            valid = jnp.ones((q.shape[0], 1, 1), bool)
            if core:
                return ca.attend_selected(
                    q, own, qi, ki, w, valid, ctx, layer, cfg.dtype,
                    values=values, scale=scale)
            scores = jnp.concatenate(
                [ca._cached_index_scores(ctx, layer, qi, w),
                 ca.index_scores(qi, ki, w, valid)], axis=-1)
            return ca.select_mask(scores, layout.select)
        return jax.jit(run, static_argnames="group")

    def timed(f, *args, n=20, **kw):
        jax.block_until_ready(f(*args, **kw))
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*args, **kw)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    out = {}
    for lanes in (16, 32):
        prompts = np.clip(rng.lognormal(np.log(4096), 0.7, lanes), 1024,
                          16384)
        lengths = {
            "cell": np.sort((prompts + rng.uniform(0, 384, lanes))
                            .astype(np.int32))[::-1].copy(),
            "full": np.full(lanes, 16384, np.int32)}
        tables = jnp.asarray(rng.integers(
            1, engine["num_blocks"], (lanes, -(-longest // page))), jnp.int32)
        k = jax.random.split(jax.random.PRNGKey(lanes), 5)
        rows = [jax.random.normal(kk, shape, jnp.float32).astype(cfg.dtype)
                for kk, shape in zip(k, (
                    (lanes, 1, H, row), (lanes, 1, row),
                    (lanes, 1, cfg.index_n_heads, cfg.index_head_dim),
                    (lanes, 1, cfg.index_head_dim)))]
        w = jax.random.normal(k[4], (lanes, 1, cfg.index_n_heads))
        for name, n in lengths.items():
            args = (latent_pool, index_pool, tables, jnp.asarray(n), *rows, w)
            group = ModelRunner.lanes_per_group(lanes)
            whole = timed(step(True), *args, group=group)
            select = timed(step(False), *args, group=group)
            out[f"{lanes}_lanes.{name}"] = {
                "mean_length": float(np.mean(n)), "step_ms": whole,
                "select_ms": select, "core_ms": whole - select}
            print(lanes, name, json.dumps(out[f"{lanes}_lanes.{name}"]),
                  flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "decode_read_glm_5.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
