"""The pieces of a latent kind's read timed alone on the chip, one layer at
the glm-5 cell's real shapes, by hand (the readings that chose the exact
top-k's form and the chunk's form: PERF.md section 6, PR 40, findings 1
and 2):

    chiprun -- python3 benchmark/selftest/chip_select_pieces_glm_5.py

`lax.top_k` against the bisection over the float's ordered bits (32 masked
counts) for the k-th largest of 2,048, with and without the tie rule, at a
decode step's 32 x 17,408 scores and a chunk's 256 x 17,664; the
compaction of a mask to rising slots by one-hot products over blocks of
128; a gather of 32 x 2,048 and of 256 x 2,048 latent rows from the pool;
a chunk's core over gathered rows against the fold of whole tiles under
the choice as a mask at 4,096 and 16,384 slots. Uses nothing of the
program: these are the forms that were weighed, most of them not kept.
Writes chiprun_out/bench_select.json."""
import json
import os
import sys
import time
sys.path.insert(0, os.getcwd())
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

def timeit(f, *a, n=10):
    r = f(*a); jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e3

out = {}
key = jax.random.PRNGKey(0)
K = 2048

def ordered_bits(x):
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 != 0, ~b, b | jnp.uint32(0x80000000))

def kth_bisect(x, k):
    keys = ordered_bits(x)
    def grow(i, lo):
        bit = jax.lax.shift_left(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        cand = lo | bit
        n = jnp.sum(keys >= cand, axis=-1, keepdims=True)
        return jnp.where(n >= k, cand, lo)
    lo = jax.lax.fori_loop(0, 32, grow, jnp.zeros(x.shape[:-1] + (1,), jnp.uint32))
    return lo  # ordered bits of the k-th largest

def compact(mask, k):
    B, S = mask.shape
    nb = S // 128
    m = mask.reshape(B, nb, 128)
    cnt = m.sum(-1)
    ends = jnp.cumsum(cnt, -1)
    off = ends - cnt
    j = jnp.arange(k)
    blk = jnp.sum(ends[:, None, :] <= j[None, :, None], -1)
    onehot = (blk[..., None] == jnp.arange(nb)).astype(jnp.bfloat16)
    local = jnp.cumsum(m, -1) * m
    rows = jnp.einsum("bkn,bnl->bkl", onehot, local.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    offs = jnp.einsum("bkn,bn->bk", onehot.astype(jnp.float32), off.astype(jnp.float32))
    r = j[None] - offs.astype(jnp.int32) + 1
    within = jnp.argmax(rows == r[..., None].astype(jnp.float32), -1)
    return jnp.minimum(blk, nb - 1) * 128 + within, blk < nb

for name, shape in (("decode", (32, 17408)), ("chunk", (256, 17664))):
    x = jax.random.normal(key, shape, jnp.float32)
    out[f"{name}.lax_top_k_ms"] = timeit(jax.jit(lambda x: jax.lax.top_k(x, K)), x)
    out[f"{name}.kth_bisect_ms"] = timeit(jax.jit(lambda x: kth_bisect(x, K)), x)
    def sel(x):
        keys = ordered_bits(x); kth = kth_bisect(x, K)
        return compact(keys >= kth, K)
    if name == "decode":
        out[f"{name}.bisect_compact_ms"] = timeit(jax.jit(sel), x)
        idx, ok = jax.jit(sel)(x)
        ref = np.sort(np.asarray(jax.lax.top_k(x, K)[1]), -1)
        out["decode.compact_exact"] = bool((np.asarray(idx) == ref).all() and np.asarray(ok).all())
    def mask_of(x):
        kth = jax.lax.top_k(x, K)[0][..., -1:]
        above = x > kth; tied = x == kth
        spare = K - jnp.sum(above, -1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, -1) <= spare))
    out[f"{name}.mask_topk_ties_ms"] = timeit(jax.jit(mask_of), x)
    def mask_b(x):
        keys = ordered_bits(x); kth = kth_bisect(x, K)
        above = keys > kth; tied = keys == kth
        spare = K - jnp.sum(above, -1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, -1) <= spare))
    out[f"{name}.mask_bisect_ties_ms"] = timeit(jax.jit(mask_b), x)
    print(json.dumps(out), flush=True)

# the gathers
pool = jnp.zeros((5, 24576, 16, 640), jnp.bfloat16) + 1
tables = jax.random.randint(key, (32, 1088), 1, 24576)
slots = jnp.sort(jax.random.randint(key, (32, K), 0, 16000), -1)
def gather(pool, tables, slots, layer):
    pages = jnp.take_along_axis(tables, slots // 16, axis=1)
    return pool[layer, pages, slots % 16]
out["decode.gather_32x2048_ms"] = timeit(jax.jit(gather), pool, tables, slots, 2)
slots_c = jnp.sort(jax.random.randint(key, (256, K), 0, 16000), -1)
tab1 = jnp.broadcast_to(tables[:1], (256, 1088))
out["chunk.gather_256x2048_ms"] = timeit(jax.jit(gather), pool, tab1, slots_c, 2)
q = jax.random.normal(key, (256, 64, 640), jnp.bfloat16)
def gathered_core(pool, tab, slots, q):
    rows = gather(pool, tab, slots, 2)  # (256, K, 640)
    s = jnp.einsum("thd,tkd->thk", q, rows, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s, -1).astype(jnp.bfloat16)
    return jnp.einsum("thk,tkd->thd", p, rows[..., :512])
out["chunk.gathered_core_ms"] = timeit(jax.jit(gathered_core), pool, tab1, slots_c, q, n=5)
def masked_core(pool, table, chosen, q, n_tiles):
    # fold tiles of 1024 slots under a mask, running softmax
    def step(t, c):
        m, l, acc = c
        rows = pool[2, jax.lax.dynamic_slice_in_dim(table, t * 64, 64)].reshape(1024, 640)
        s = jnp.einsum("thd,sd->hts", q, rows, preferred_element_type=jnp.float32)
        valid = jax.lax.dynamic_slice_in_dim(chosen, t * 1024, 1024, 1)
        s = jnp.where(valid[None], s, -1e30)
        m2 = jnp.maximum(m, s.max(-1)); a = jnp.exp(m - m2); p = jnp.exp(s - m2[..., None])
        return m2, a * l + p.sum(-1), a[..., None] * acc + jnp.einsum("hts,sd->htd", p.astype(jnp.bfloat16), rows[:, :512], preferred_element_type=jnp.float32)
    c = (jnp.full((64, 256), -1e30), jnp.zeros((64, 256)), jnp.zeros((64, 256, 512)))
    return jax.lax.fori_loop(0, n_tiles, step, c)[2]
chosen = jax.random.uniform(key, (256, 17408)) < 0.2
for S in (4096, 16384):
    out[f"chunk.masked_core_S{S}_ms"] = timeit(jax.jit(masked_core), pool, tables[0], chosen, q, S // 1024, n=5)
print(json.dumps(out), flush=True)
os.makedirs("chiprun_out", exist_ok=True)
json.dump(out, open("chiprun_out/bench_select.json", "w"), indent=1)
