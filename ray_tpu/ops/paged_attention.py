"""The cached-context read of a decode or verify step, one Pallas TPU
kernel a layer over the serve.llm KV page pool.

`ops/context_attention.py` `attend_cached` (a full kind) and
`attend_latent` (a latent kind read whole) hand a program with few rows a
lane (decode, T = 1; verify, T = K + 1) to `paged_attention` below where
their predicate (`context_attention.reads_by_kernel`) allows; every other
program keeps the XLA tile loops there. The kernel

- takes the K and the V pool whole, as they lie (`KVLayout.shape`: one
  lane-dense row of ``n_kv_head * head_dim`` a token), in HBM
  (``pl.ANY``), with the layer index, the lanes' block tables and their
  lengths by scalar prefetch, and copies `pages_a_step` pages a step
  into double-buffered VMEM with its own async copies: the copies of
  step n + 1, which may be the next lane's first, are started before
  step n is computed (the shape of JAX's public
  ``pallas.ops.tpu.paged_attention``);
- takes a kind with ONE pool alike (`KVLayout.v_row` 0: latent attention
  read whole, xing4's 640-lane row): one copy a page, the step's values
  the first lanes of its K buffer (512 of 640: a slice in VMEM), the
  scale the caller's, and with one "KV head" under all query heads the
  accumulator is the output;
- stops at each lane's own length, to the page: a lane of 96 slots
  copies 6 pages, a padded lane of a bucket none. One flat loop runs
  the steps of every lane, ``max(1, ceil(length / slots a step))`` a
  lane: nothing is grouped, ordered or bounded by the longest lane;
- forms the products on the page rows as they lie, on the MXU: the
  lane's queries come as a block-diagonal ``(C, row)`` matrix, column
  block ``c = (r, t, h)`` holding query head ``(h, r)`` of row t in KV
  head h's lanes and zero elsewhere, so that ``Q_bd (C, row) . K_pages
  (slots, row)^T`` is every head's scores at once, and ``P (C, slots) @
  V_pages (slots, v_row)`` holds every head's output in its own lanes of
  its own rows (the diagonal blocks; the rest is discarded). Operands in
  the pool's dtype, float32 accumulation, the probabilities cast to the
  pool's dtype before the value product as `context_attention._weigh`
  does;
- keeps the running softmax (max, sum, accumulator: float32) in VMEM
  across a lane's steps, started on the program's own rows (which are
  never in the pages: they are scattered after the step), under the
  same softmax.

``interpret=True`` runs the same kernel through the Pallas interpreter
on the CPU (the tests); `paged_attention_reference` is its full-width
oracle, every slot of the tables read and masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = -1e30  # the score of a slot no row sees; exp(MASKED - m) is 0

# K and V bytes a step copies, at most: a step costs about a microsecond
# beside its bytes (its pages' copies are issued one by one, its products
# wait for them), so it moves a MB or two: 16 pages of 82 KB at gpt2-large
# (631 GB/s of valid rows at 4 lanes x 1,000 slots on the v5e, 524 at 8
# pages), 16 of 131 KB at OLMoE (708), 128 of 16 KB at the nemotron_h cut
# (221: a copy is one page, and at 8 KB a copy their issue is what is
# left; PERF.md section 6, PR 41), 64 of 20 KB at xing4's one pool (499 on
# the cell's lanes of 2,790-24,049 slots, PR 52)
STEP_BYTES = 2 * 1024 * 1024
# a step's slots are whole lane tiles of the scores where the pages allow
STEP_SLOTS_MIN = 128
# the program's own rows join as one more block, padded to a sublane
# tile of the pool's dtype
OWN_ROWS = 16
# pages whose copies one turn of the loop starts, written out, for a kind
# with one pool: 4, 8, 16 and all 64 of a step read 480, 499, 507 and 557
# GB/s on the xing4 cell's lanes, and every copy written out costs a
# program's trace some 10 ms a kernel (all 64, at two places, in each of
# six layers of six decode programs: 128 s of a replica's warm-up, of
# which one trace for a program's layers left 9; PERF.md section 6, PR 52)
STARTS_A_TURN = 8


def pages_a_step(layout, itemsize: int, table_pages: int) -> int:
    """Pages the kernel copies a step for pools of `layout`: the largest
    power of two under `STEP_BYTES` of K and V (of K alone where the kind
    has no V row), at least `STEP_SLOTS_MIN` slots, at most the table."""
    page = layout.block_size * (layout.row + layout.v_row) * itemsize
    pages = max(1, STEP_BYTES // page)
    pages = 1 << (pages.bit_length() - 1)
    return min(max(pages, -(-STEP_SLOTS_MIN // layout.block_size)),
               table_pages)


def _read_kernel(layer_ref, tables_ref, lengths_ref, q_ref, ko_ref, vo_ref,
                 bias_ref, *refs, n_pools, scale, pages, bs, heads, v_width):
    # the kind's pools (K and V, or K alone), the output, a double buffer
    # a pool, a semaphore a pool and buffer, the running softmax
    pools, (o_ref, *bufs, sems, m_s, l_s, acc_s) = refs[:n_pools], \
        refs[n_pools:]
    lanes, v_row = o_ref.shape[0], acc_s.shape[1]
    S = pages * bs
    layer = layer_ref[0]
    k_buf = bufs[0]
    # A copy's issue and its wait are the scalar core's, one after the
    # other with the step's products: at 20 KB a copy (xing4's page) a
    # loop that starts one copy a turn and a loop that waits for one take
    # 2.7 us of a 3.8 us step, 349 GB/s of valid rows on the xing4 cell's
    # lanes; started `STARTS_A_TURN` a turn and, where a step's pages are
    # all there, waited for at once it is 499 (PERF.md section 6, PR
    # 52). The kinds with K and V pools keep the loops their cells were
    # measured with (tests/test_paged_attention.py holds their kernel to
    # its text) until a PR measures them under it.
    straight = n_pools == 1

    def values_of(slot):
        if n_pools == 1:  # the K row's first lanes: a slice in VMEM
            return k_buf[slot, :, :v_row]
        return bufs[1][slot]

    def copies(lane, blk, slot, j):
        page = tables_ref[lane, blk * pages + j]
        dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
        return tuple(pltpu.make_async_copy(pool.at[layer, page],
                                           buf.at[slot, dst],
                                           sems.at[i, slot])
                     for i, (pool, buf) in enumerate(zip(pools, bufs)))

    def each_page(lane, blk, slot, do, a_turn: int = 1):
        """`do` on the copies of block `blk`'s pages below the lane's
        length, `a_turn` pages a turn of the loop: none for a lane with
        no context."""
        left = lengths_ref[lane] - blk * S
        n = jnp.clip((left + bs - 1) // bs, 0, pages)

        def one(j, carry):
            for c in copies(lane, blk, slot, j):
                do(c)
            return carry

        if a_turn == 1:
            jax.lax.fori_loop(0, n, one, 0)
            return

        def several(t, carry):
            for j in range(a_turn):
                one(t * a_turn + j, carry)
            return carry

        turns = n // a_turn
        jax.lax.fori_loop(0, turns, several, 0)
        jax.lax.fori_loop(turns * a_turn, n, one, 0)

    def start_pages(lane, blk, slot):
        each_page(lane, blk, slot, lambda c: c.start(),
                  STARTS_A_TURN if straight else 1)

    def wait_pages(lane, blk, slot):
        """For the copies `start_pages` started. Where `straight`, a
        block whose pages are all below the lane's length (every block
        of a lane but its last) waits once, for as many bytes as a buffer
        holds: its copies all signal the one semaphore."""
        if not straight:
            each_page(lane, blk, slot, lambda c: c.wait())
            return
        whole = lengths_ref[lane] - blk * S >= S

        @pl.when(whole)
        def _():
            for i, buf in enumerate(bufs):
                pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                                      sems.at[i, slot]).wait()

        @pl.when(jnp.logical_not(whole))
        def _():
            each_page(lane, blk, slot, lambda c: c.wait())

    def fold(s, values):
        """Scores s (C, n) float32 and their values (n, v_row) folded
        into the running softmax."""
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    def scores(q, keys):  # (C, row) . (n, row)^T -> (C, n) float32
        return jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    def step(n, at):
        lane, blk = at
        slot = n % 2
        length = lengths_ref[lane]
        last = (blk + 1) * S >= length
        nxt = (jnp.where(last, lane + 1, lane), jnp.where(last, 0, blk + 1))

        @pl.when(nxt[0] < lanes)
        def _():
            start_pages(*nxt, 1 - slot)

        q = q_ref[lane]

        @pl.when(blk == 0)
        def _():  # the running softmax starts on the program's own rows
            m_s[...] = jnp.full_like(m_s, MASKED)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)
            fold(scores(q, ko_ref[lane]) + bias_ref[lane], vo_ref[lane])

        @pl.when(blk * S < length)
        def _():
            wait_pages(lane, blk, slot)
            s = scores(q, k_buf[slot])
            at_slot = blk * S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            fold(jnp.where(at_slot < length, s, MASKED), values_of(slot))

        @pl.when(last)
        def _():
            out = acc_s[...] / l_s[...]
            if heads == 1:  # every row's output is its whole row
                o_ref[lane] = out.astype(o_ref.dtype)
                return
            # row c = (rt, h) of the accumulator holds head h's output
            # in lanes [h * v_width, (h + 1) * v_width)
            c = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            lane_at = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
            picked = []
            for rt in range(o_ref.shape[1]):
                h = c - rt * heads
                own = (h >= 0) & (h < heads) & (lane_at >= h * v_width) \
                    & (lane_at < (h + 1) * v_width)
                picked.append(jnp.sum(jnp.where(own, out, 0.0), axis=0,
                                      keepdims=True))
            o_ref[lane] = jnp.concatenate(picked, axis=0).astype(o_ref.dtype)

        return nxt

    # a slot past a lane's length is never copied: what the buffer its
    # values come from holds there must be a number (its weight is 0)
    bufs[-1][...] = jnp.zeros_like(bufs[-1])
    start_pages(0, 0, 0)
    total = jax.lax.fori_loop(
        0, lanes,
        lambda b, n: n + jnp.maximum(1, (lengths_ref[b] + S - 1) // S), 0)
    jax.lax.fori_loop(0, total, step, (0, 0))


def paged_attention(q, k, v, own_valid, k_pages, v_pages, tables, lengths,
                    *, layout, layer, dtype, scale: float | None = None,
                    interpret: bool = False):
    """q (B, T, HK, R, D) attends, under one softmax scaled by `scale`
    (``1 / sqrt(D)`` where none is given), to lane b's cached rows ``[0,
    lengths[b])`` of layer `layer` (a traced i32, or an int) of the
    pools, through ``tables (B, pages a lane)``, and to the program's own
    rows k (B, T, HK, D), v (B, T, HK, Dv) where `own_valid` (B, T, T)
    allows, of which every row sees one -> (B, T, HK, R, Dv) in `dtype`.
    The pools lie as `layout` (serve/llm/cache.py `KVLayout`) says; a
    table entry at or past ``ceil(lengths[b] / block_size)`` is never
    read. A kind with no V row (`layout.v_row` 0: a latent kind read
    whole, one "KV head" whose row every query head reads) has its
    values in the first Dv lanes of its K row, own and cached alike:
    `v_pages` is not read."""
    B, T, HK, R, D = q.shape
    Dv = v.shape[-1]
    rows, C = R * T, HK * R * T
    row, v_row = HK * D, HK * Dv
    one_pool = layout.v_row == 0
    if one_pool and HK != 1:
        raise ValueError(f"a kind with no V row has one KV head, not {HK}")
    pages = pages_a_step(layout, k_pages.dtype.itemsize, tables.shape[1])
    S = pages * layout.block_size
    # column block c = (r, t, h): query head (h, r) of row t in KV head
    # h's lanes of a page row, zero in every other head's
    qr = jnp.transpose(q, (0, 3, 1, 2, 4)).reshape(B, rows, 1, row)
    in_head = (jnp.arange(row) // D)[None, :] == jnp.arange(HK)[:, None]
    q_bd = (qr if HK == 1 else jnp.where(in_head, qr, 0)).reshape(B, C, row)
    pad = -T % OWN_ROWS
    seen = jnp.broadcast_to(own_valid[:, None, :, None, :],
                            (B, R, T, HK, T)).reshape(B, C, T)
    bias = jnp.pad(jnp.where(seen, 0.0, MASKED).astype(jnp.float32),
                   ((0, 0), (0, 0), (0, pad)), constant_values=MASKED)
    own = ((0, 0), (0, pad), (0, 0))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = (k_pages, v_pages)[:1 if one_pool else 2]
    out = pl.pallas_call(
        functools.partial(
            _read_kernel, n_pools=len(pools),
            scale=1.0 / (D ** 0.5) if scale is None else scale, pages=pages,
            bs=layout.block_size, heads=HK, v_width=Dv),
        out_shape=jax.ShapeDtypeStruct((B, rows, v_row), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, whole, whole, whole] + [in_hbm] * len(pools),
            out_specs=whole,
            scratch_shapes=[
                *(pltpu.VMEM((2, S, pool.shape[-1]), pool.dtype)
                  for pool in pools),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((C, 1), jnp.float32),
                pltpu.VMEM((C, 1), jnp.float32),
                pltpu.VMEM((C, v_row), jnp.float32),
            ]),
        name="ctx_read_paged",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_bd, jnp.pad(k.reshape(B, T, row), own),
      jnp.pad(v.reshape(B, T, v_row), own), bias, *pools)
    return jnp.transpose(out.reshape(B, R, T, HK, Dv), (0, 2, 3, 1, 4))


def paged_attention_reference(q, k, v, own_valid, k_pages, v_pages, tables,
                              lengths, *, layout, layer, dtype,
                              scale: float | None = None):
    """The kernel's oracle (tests), same operands: every slot the tables
    hold read and masked by the lane's length, one full-width softmax
    (`context_attention.softmax_over`)."""
    from ray_tpu.ops.context_attention import softmax_over

    B, T = q.shape[:2]
    keys = layout.read(k_pages, layer, tables)
    values = layout.read(v_pages, layer, tables) if layout.v_row \
        else keys[..., :v.shape[-1]]
    cached = jnp.broadcast_to(
        jnp.arange(keys.shape[1])[None, None, :] < lengths[:, None, None],
        (B, T, keys.shape[1]))
    return softmax_over(q, [(keys, values, cached), (k, v, own_valid)],
                        1.0 / (q.shape[-1] ** 0.5) if scale is None
                        else scale, dtype)
