"""Paged attention — pallas TPU kernel over the serve.llm KV page pool.

The dense decode/verify programs gather each lane's pages into a
``(L, S, max_blocks_per_seq * block_size, H_kv, D)`` context before
attending (runner.py) — O(max_model_len) HBM traffic per step
regardless of how long the sequence actually is. This kernel is the
vLLM-PagedAttention shape instead (PAPERS.md): queries index the page
pool *in place* through the block table, one page per grid step, with
the layer index, the table and the context lengths delivered via scalar
prefetch so the page id is known before the page's DMA is issued.

Operands:

- ``q``                (S, W, H, D)  — W query positions per sequence:
  W=1 is plain decode, W=K+1 is the speculative verify window;
- ``own_k``/``own_v``  (S, W, H_kv, D) — the window's OWN keys/values
  (they are never in the pages: decode/verify scatter them after the
  step), attended causally within the window;
- ``k_pages``/``v_pages`` — the pool as the runner holds it,
  (L, num_blocks, block_size, H_kv, D) with ``layer`` (a traced i32)
  selecting the layer, or one layer's (num_blocks, block_size, H_kv, D)
  with ``layer=None``. The models scan over layer INDICES and close
  over the whole pool: slicing the pool per layer would copy it;
- ``tables``           (S, max_blocks_per_seq) i32 — logical page i of
  sequence s lives in physical page ``tables[s, i]`` (padding points at
  the null page 0, which the length mask excludes anyway);
- ``ctx_len``          (S,) i32 — valid cached slots (positions
  < ctx_len[s] are real; everything else in the mapped pages is
  garbage past the lane's frontier).

Blocking. Mosaic requires the last two dims of every block to be
(8, 128)-divisible or the array's full dims, so a block is never cut
inside ``(H_kv, D)``: one grid step holds one whole page
``(block_size, H_kv, D)``, and queries are regrouped outside the kernel
to ``(S, W, rep, H_kv, D)`` (head ``h = hk * rep + r``). Grid is
(S, max_blocks_per_seq), pages innermost and sequential, carrying the
online-softmax state (running max, sum, f32 accumulator) in VMEM
scratch like ops/flash_attention.py; pages wholly past ``ctx_len`` are
skipped with ``pl.when``; the final grid step folds in the causal
own-window block and normalizes. The arithmetic is a VPU
multiply-reduce per (window row, group member) over all KV heads at
once — decode is bound by reading pages, and this keeps ``(H_kv, D)``
in its native layout with no in-kernel relayout.

``interpret=True`` runs the same kernel through the pallas interpreter
on CPU (tests, parity gates); on TPU it compiles for real. The dense
reference (`paged_attention_reference`) is the parity oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _paged_kernel(layer_ref, tables_ref, ctxlen_ref, q_ref, ko_ref, vo_ref,
                  kp_ref, vp_ref, o_ref, acc, m_s, l_s, *, scale, nb, bs):
    del layer_ref, tables_ref  # consumed by the index maps
    s_i = pl.program_id(0)
    b = pl.program_id(1)
    W, rep = q_ref.shape[0], q_ref.shape[1]

    @pl.when(b == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    ctx = ctxlen_ref[s_i]

    def _accum(i, k, v, valid):
        # row i = (w, r): one query per KV head, q (HK, D); k/v (N, HK, D)
        # f32; valid (N, 1, 1). VPU formulation: the (HK, D) minor dims of
        # the pool never leave their native layout.
        q = q_ref[i // rep, i % rep].astype(jnp.float32)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)  # (N, HK, 1)
        m_prev = m_s[i, :, :1]  # (HK, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])
        l_new = alpha * l_s[i, :, :1] + jnp.sum(p, axis=0)
        acc[i] = acc[i] * alpha + jnp.sum(p * v, axis=0)
        m_s[i] = jnp.broadcast_to(m_new, m_s.shape[1:])
        l_s[i] = jnp.broadcast_to(l_new, l_s.shape[1:])

    @pl.when(b * bs < ctx)
    def _page():
        k = kp_ref[...].astype(jnp.float32)
        v = vp_ref[...].astype(jnp.float32)
        cols = b * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        for i in range(W * rep):
            _accum(i, k, v, cols < ctx)

    @pl.when(b == nb - 1)
    def _own_and_emit():
        k = ko_ref[...].astype(jnp.float32)
        v = vo_ref[...].astype(jnp.float32)
        x = jax.lax.broadcasted_iota(jnp.int32, (W, 1, 1), 0)
        for i in range(W * rep):
            _accum(i, k, v, x <= i // rep)
            l = l_s[i, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[i // rep, i % rep] = (acc[i] / l_safe).astype(o_ref.dtype)


def paged_attention(q, own_k, own_v, k_pages, v_pages, tables, ctx_len,
                    *, layer=None, sm_scale: float | None = None,
                    interpret: bool = False):
    """One layer of paged attention; see the module docstring for the
    operand layout. Returns (S, W, H, D) in q's dtype. Every query row
    attends [cached slots < ctx_len[s]] ++ [own window, causally]."""
    S, W, H, D = q.shape
    HK = own_k.shape[2]
    rep = H // HK
    if layer is None:  # one layer's (num_blocks, bs, HK, D) pool
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    bs = k_pages.shape[2]
    maxB = tables.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    # head h = hk * rep + r  ->  (S, W, rep, HK, D): every block then ends
    # in the full (HK, D) dims of its array
    qg = q.reshape(S, W, HK, rep, D).swapaxes(2, 3)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    own = vmem((None, W, HK, D), lambda s, b, l, t, c: (s, 0, 0, 0))
    page = vmem((None, None, bs, HK, D),
                lambda s, b, l, t, c: (l[0], t[s, b], 0, 0, 0))
    qspec = vmem((None, W, rep, HK, D),
                 lambda s, b, l, t, c: (s, 0, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, nb=maxB, bs=bs),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, maxB),
            in_specs=[qspec, own, own, page, page],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((W * rep, HK, D), jnp.float32),
                pltpu.VMEM((W * rep, HK, 128), jnp.float32),
                pltpu.VMEM((W * rep, HK, 128), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      tables.astype(jnp.int32), ctx_len.astype(jnp.int32),
      qg, own_k, own_v, k_pages, v_pages)
    return out.swapaxes(2, 3).reshape(S, W, H, D)


def paged_attention_reference(q, own_k, own_v, k_pages, v_pages, tables,
                              ctx_len):
    """Dense jnp oracle for the kernel (tests): gather pages through the
    table, mask by ctx_len, causal own window. Same operand layout."""
    S, W, H, D = q.shape
    HK = own_k.shape[2]
    bs = k_pages.shape[1]
    maxB = tables.shape[1]
    C = maxB * bs
    rep = H // HK
    k_ctx = k_pages[tables].reshape(S, C, HK, D)
    v_ctx = v_pages[tables].reshape(S, C, HK, D)
    k_ctx = jnp.repeat(k_ctx, rep, axis=2)
    v_ctx = jnp.repeat(v_ctx, rep, axis=2)
    ko = jnp.repeat(own_k, rep, axis=2)
    vo = jnp.repeat(own_v, rep, axis=2)
    scale = 1.0 / (D**0.5)
    s_ctx = jnp.einsum("swhd,schd->shwc", q, k_ctx).astype(jnp.float32)
    s_own = jnp.einsum("swhd,sxhd->shwx", q, ko).astype(jnp.float32)
    s = jnp.concatenate([s_ctx, s_own], axis=-1) * scale
    ctx_valid = jnp.arange(C)[None, :] < ctx_len[:, None]  # (S, C)
    causal = jnp.tril(jnp.ones((W, W), dtype=bool))
    valid = jnp.concatenate(
        [jnp.broadcast_to(ctx_valid[:, None, :], (S, W, C)),
         jnp.broadcast_to(causal[None], (S, W, W))], axis=-1)
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("shwc,schd->swhd", p[..., :C],
                     v_ctx.astype(jnp.float32)) \
        + jnp.einsum("shwx,sxhd->swhd", p[..., C:],
                     vo.astype(jnp.float32))
    return att.astype(q.dtype)
