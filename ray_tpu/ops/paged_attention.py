"""Paged attention — pallas TPU kernel over the serve.llm KV page pool.

The dense decode/verify programs gather, layer by layer, a group of
lanes' pages into a ``(lanes, tiles * tile, H_kv, D)`` context before
attending: whole tiles of pages up to the group's longest lane
(ops/context_attention.py), relaid out into heads on the way. This
kernel is the vLLM-PagedAttention shape instead (PAPERS.md): queries
index the page pool *in place* through the block table, one page per
grid step (every page of the table is a step, pages past the lane's
length skipped inside it), with the layer index, the table and the
context lengths delivered via scalar prefetch so the page id is known
before the page's DMA is issued.

Operands:

- ``q``                (S, W, H, D)  — W query positions per sequence:
  W=1 is plain decode, W=K+1 is the speculative verify window;
- ``own_k``/``own_v``  (S, W, H_kv, D) — the window's OWN keys/values
  (they are never in the pages: decode/verify scatter them after the
  step), attended causally within the window;
- ``k_pages``/``v_pages`` — the whole pool as the runner holds it, and
  ``layout`` — the `serve/llm/cache.py` ``KVLayout`` that says how it
  lies (one lane-dense row of ``H_kv * D`` per token, head ``h`` in
  lanes ``[h * D, (h + 1) * D)``) and gives the kernel its page block;
  ``layer`` (a traced i32, or an int) selects the layer. The models scan
  over layer INDICES and close over the whole pool: slicing the pool per
  layer, or reshaping it, would copy it;
- ``tables``           (S, max_blocks_per_seq) i32 — logical page i of
  sequence s lives in physical page ``tables[s, i]`` (padding points at
  the null page 0, which the length mask excludes anyway);
- ``ctx_len``          (S,) i32 — valid cached slots (positions
  < ctx_len[s] are real; everything else in the mapped pages is
  garbage past the lane's frontier).

Blocking. One grid step holds one whole page ``(block_size, H_kv * D)``,
which the ``(8, 128)`` tiling divides (768 lanes at gpt2-small, 1280 at
gpt2-large, 1024 at 8 x 128), and nothing in the kernel ever leaves that
row: queries are regrouped outside the kernel to ``(S, W, rep,
H_kv * D)`` (head ``h = hk * rep + r``, so one query row lines up with
one page row, lane for lane), the window's own keys and values are
flattened the same way, and the online-softmax state (running max, sum,
f32 accumulator; VMEM scratch like ops/flash_attention.py) is kept per
lane, every lane of a head holding that head's value. Grid is
(S, max_blocks_per_seq), pages innermost and sequential; pages wholly
past ``ctx_len`` are skipped with ``pl.when``; the final grid step folds
in the causal own-window block and normalizes. The arithmetic is a VPU
multiply per (window row, group member) over all KV heads at once, and
the sum over a head's ``D`` lanes is a butterfly of lane rotations
(``_head_sums``; ``D`` a power of two) that leaves the total in every
lane of the head — decode is bound by reading pages, and there is no
in-kernel relayout.

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


def _head_sums(x, head_dim):
    """x (N, H_kv * D) -> the same shape, every lane holding the sum over
    its own head's D lanes: log2(D) butterfly steps, lane i adding lane
    i ^ d (heads are D-aligned and D is a power of two, so the partner
    never leaves the head)."""
    row = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = 1
    while d < head_dim:
        x = x + jnp.where((lane & d) != 0, pltpu.roll(x, d, 1),
                          pltpu.roll(x, row - d, 1))
        d *= 2
    return x


def _paged_kernel(layer_ref, tables_ref, ctxlen_ref, q_ref, ko_ref, vo_ref,
                  kp_ref, vp_ref, o_ref, acc, m_s, l_s, *, scale, nb, bs,
                  head_dim):
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
        # row i = (w, r): one query per KV head, flattened like a page
        # row, q (1, row); k/v (N, row) f32; valid (N, row). State rows
        # are (1, row), a head's value repeated over its lanes.
        one = pl.ds(i, 1)
        q = q_ref[i // rep, pl.ds(i % rep, 1), :].astype(jnp.float32)
        s = _head_sums(k * q, head_dim) * scale
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        m_prev = m_s[one, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[one, :] = alpha * l_s[one, :] + jnp.sum(p, axis=0,
                                                    keepdims=True)
        acc[one, :] = acc[one, :] * alpha + jnp.sum(p * v, axis=0,
                                                    keepdims=True)
        m_s[one, :] = m_new

    @pl.when(b * bs < ctx)
    def _page():
        k = kp_ref[...].astype(jnp.float32)
        v = vp_ref[...].astype(jnp.float32)
        cols = b * bs + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        for i in range(W * rep):
            _accum(i, k, v, cols < ctx)

    @pl.when(b == nb - 1)
    def _own_and_emit():
        k = ko_ref[...].astype(jnp.float32)
        v = vo_ref[...].astype(jnp.float32)
        x = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        for i in range(W * rep):
            _accum(i, k, v, x <= i // rep)
            l = l_s[pl.ds(i, 1), :]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[i // rep, pl.ds(i % rep, 1), :] = (
                acc[pl.ds(i, 1), :] / l_safe).astype(o_ref.dtype)


def paged_attention(q, own_k, own_v, k_pages, v_pages, tables, ctx_len,
                    *, layout, layer=0, sm_scale: float | None = None,
                    interpret: bool = False):
    """One layer of paged attention; see the module docstring for the
    operand layout. Returns (S, W, H, D) in q's dtype. Every query row
    attends [cached slots < ctx_len[s]] ++ [own window, causally]."""
    S, W, H, D = q.shape
    HK = own_k.shape[2]
    rep = H // HK
    if D & (D - 1):
        raise ValueError(f"head_dim {D} is not a power of two")
    bs, row = layout.block_size, layout.row
    maxB = tables.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    # head h = hk * rep + r  ->  (S, W, rep, HK * D): a query row then
    # lines up with a page row, lane for lane
    qg = q.reshape(S, W, HK, rep, D).swapaxes(2, 3).reshape(S, W, rep, row)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    own = vmem((None, W, row), lambda s, b, l, t, c: (s, 0, 0))
    page = vmem(layout.page_block(),
                lambda s, b, l, t, c: layout.page_index(l[0], t[s, b]))
    qspec = vmem((None, W, rep, row), lambda s, b, l, t, c: (s, 0, 0, 0))
    state = pltpu.VMEM((W * rep, row), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, nb=maxB, bs=bs,
                          head_dim=D),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, maxB),
            in_specs=[qspec, own, own, page, page],
            out_specs=qspec,
            scratch_shapes=[state, state, state],
        ),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      tables.astype(jnp.int32), ctx_len.astype(jnp.int32),
      qg, own_k.reshape(S, W, row), own_v.reshape(S, W, row),
      k_pages, v_pages)
    return out.reshape(S, W, rep, HK, D).swapaxes(2, 3).reshape(S, W, H, D)


def paged_attention_reference(q, own_k, own_v, k_pages, v_pages, tables,
                              ctx_len, *, layout, layer=0):
    """Dense jnp oracle for the kernel (tests): read the layer's pages
    through the table, mask by ctx_len, causal own window. Same operand
    layout."""
    S, W, H, D = q.shape
    HK = own_k.shape[2]
    rep = H // HK
    k_ctx = layout.read(k_pages, layer, tables)  # (S, C, HK, D)
    v_ctx = layout.read(v_pages, layer, tables)
    C = k_ctx.shape[1]
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
