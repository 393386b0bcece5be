"""Attention over a lane's cached context, read to the lane's length.

The dense serve programs (decode, chunk, verify of every family) attend a
program's query rows to two segments under one softmax: the lane's cached
context, which lives in the KV page pool, and the program's own rows,
which are scattered into the pool only after the step. This module is the
one place that does it, for all families: they bring q grouped by KV head
(``R`` query heads a KV head: 1 for gpt2, ``n_head // n_kv_head`` for
llama and nemotron_h), their own K and V rows, which own rows a query row
may see, and a `CachedContext`.

The context is read in **tiles** of whole pages (`KVLayout.tile_pages`),
and only the tiles that the lanes reach. The lanes of a program form
groups of `CachedContext.group` consecutive rows, and a group reads
``ceil(longest length in the group / tile)`` tiles: a count traced from
the lengths, the same in every layer. The runner orders a decode step's
lanes by length, longest first, so a group's lanes are alike and the rows
that reach a tile are the program's first rows: tile t is read once, for
all of them together (`attend_cached`), under a running softmax (max, sum
and accumulator in float32). A slot past a lane's length had the weight
``exp(-1e30 - m) = 0`` when all ``max_model_len`` slots were read and
masked; not reading it changes no term of the softmax, only the order of
a float32 sum.

A program with few rows a lane (a decode step, a verify window) reads a
full kind's context, and a latent kind's that is read whole, with one
Pallas kernel a layer instead of the tile loops (`reads_by_kernel`,
ops/paged_attention.py): pages copied several a step to each lane's own
length, the heads' products formed on the page rows as they lie. The
loops stay the path of every other program (a chunk's 256 rows are a
different, MXU-bound trade), of a kind the kernel does not take (a
window, a selection, a sink, K and V of unlike widths), of a pool split
over `tensor`, and of the CPU.

A kind of layer with a **window** (`KVLayout.window`: a row sees itself
and the ``window - 1`` rows before it) has a lower end to its read as
well: of lane b's cached slots only ``(lengths[b] - window, lengths[b])``
can be seen by any row of the program, and they lie in
`KVLayout.window_pages` pages from the page that holds the first of them.
Those pages are one tile whose first page is the lane's own, read once
for all lanes with no loop (`_window_tile`), and the mask over the own
rows gets the window too. K and V rows may differ in width. A layer with
a **sink** (one learned scalar a query head that joins the softmax as a
column with no value) starts its running softmax from ``(m, l) = (sink,
1)`` and a zero accumulator.

A kind of layer that **selects** (`KVLayout.select`: latent attention
under a learned indexer) caches two rows a token, the latent row, which
is key and value of every query head at once, and the indexer's key, and
a query row attends only to the `select` slots, cached or the program's
own, that its indexer scores highest (`attend_selected`): an index-score
pass over the indexer keys in tiles to the lanes' lengths, by the same
groups, an exact top-k over cached and own slots together, and one
softmax over what was chosen: the latent tiles a lane reaches are folded
under the choice as their mask, by the same groups again, in a chunk and
in a decode step alike (PERF.md section 6, PR 40, findings 2 and 8: an
XLA gather of the chosen rows alone was measured for both; it costs a
chunk 15 times the fold, and a decode step more than the fold below a
mean context of about 6k slots a lane. Reading the chosen rows only is
a kernel's to do).

A latent kind with NO indexer (`KVLayout.v_head_dim` 0: one pool) reads
every cached slot of a lane (`attend_latent`): a decode step with the
kernel, the latent row its one KV head and the row's first lanes its
values (PERF.md section 6, PR 52); a chunk by the same fold of the latent
tiles by the same groups, the mask a lane's length.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CachedContext:
    """The cached context of a program's lanes, as the runner hands it to
    a family's forward: the pools, the lanes' block tables and how many
    cached slots of each lane are real."""

    layout: Any  # serve/llm/cache.py KVLayout
    k_pages: jax.Array
    v_pages: jax.Array
    tables: jax.Array  # (B, pages a lane) i32, whole tiles
    lengths: jax.Array  # (B,) i32: slots [0, lengths[b]) hold lane b's rows
    group: int  # lanes a group: consecutive rows, B a multiple of it
    # (B // group,) i32: the tiles that group g, or any group behind it,
    # reaches (for lanes ordered longest first: the tiles of group g);
    # None for a window kind, which reads one tile a lane
    reach: jax.Array | None

    @classmethod
    def of(cls, layout, k_pages, v_pages, tables, lengths, group: int = 1):
        """`tables` (B, n) padded with the null page to whole tiles, and
        each group's tile count, worked out once a program; for a window
        kind, padded so that a window's pages from any page on are in
        range."""
        if layout.window is not None:
            tables = jnp.pad(tables, ((0, 0), (0, layout.window_pages)))
            return cls(layout, k_pages, v_pages, tables, lengths, group, None)
        per = layout.tile_pages
        pad = -tables.shape[1] % per
        if pad:
            tables = jnp.pad(tables, ((0, 0), (0, pad)))
        tile = per * layout.block_size
        longest = jnp.max(lengths.reshape(-1, group), axis=1)
        return cls(layout, k_pages, v_pages, tables, lengths, group,
                   jax.lax.cummax((longest + tile - 1) // tile, reverse=True))

    def read(self, layer, tables):
        """Layer `layer`'s keys and values through `tables` (G, n)."""
        return (self.layout.read(self.k_pages, layer, tables),
                self.layout.read(self.v_pages, layer, tables))


# Most rows a lane of a program whose read the kernel takes: a decode
# step's one, a verify window's K + 1 (a chunk's bucket starts at 16)
KERNEL_ROWS = 8


def reads_by_kernel(layout, rows: int, sink: bool = False) -> bool:
    """Whether a program of `rows` rows a lane reads the cached context
    of a kind of layer whose pools lie as `layout` with the Pallas kernel
    (ops/paged_attention.py) and not with the tile loops below: few rows
    a lane, every earlier row seen (no window, no selection), K and V
    heads of one width or no V row at all (a latent kind read whole: its
    values are in its K row) and no sink, rows and pages that are whole
    tiles of the chip's memory, pools that lie whole on one chip (under
    the mesh in force: a pool split by heads over `tensor` keeps the
    loops, which XLA partitions), on a TPU. The one place that picks the
    path: `attend_cached` and `attend_latent` ask it for the program they
    trace, the runner for what it counts as read
    (`ModelRunner._note_context`)."""
    mesh = jax.sharding.get_abstract_mesh()
    tensor_ways = dict(mesh.shape).get("tensor", 1)
    return (jax.default_backend() == "tpu" and rows <= KERNEL_ROWS
            and layout.window is None and layout.select is None
            and not sink and layout.v_row in (layout.row, 0)
            and layout.row % 128 == 0 and layout.block_size % 16 == 0
            and layout.shard_ways(tensor_ways) == 1)


def causal_rows(chunk_mask):
    """`own_valid` (B, T, T) of a chunk or a window: row t sees its own
    rows up to t, of those `chunk_mask` (B, T) marks as real."""
    T = chunk_mask.shape[1]
    return jnp.tril(jnp.ones((T, T), dtype=bool))[None] \
        & chunk_mask[:, None, :]


def _scores(q, keys, valid, scale):
    """q (G, T, HK, R, D) against keys (G, S, HK, D) -> (G, HK, R, T, S)
    float32, -1e30 where `valid` (G, T, S) is false."""
    s = jnp.einsum("btgrd,bsgd->bgrts", q, keys).astype(jnp.float32)
    return jnp.where(valid[:, None, None], s * scale, -1e30)


def softmax_over(q, segments, scale, dtype, sink=None):
    """One softmax, in float32, over the rows of every segment ``(keys,
    values (G, S, HK, D), valid (G, T, S))``: every slot read and masked
    (a whole prompt's own rows; the tests' full-width reference). The
    segments' scores are joined, never their rows, and K and V are never
    repeated R times: the query heads of a KV head share them in the
    product. `sink` (HK, R) joins as one more column, which has no value
    and is dropped after the softmax."""
    scores = [_scores(q, keys, valid, scale) for keys, _, valid in segments]
    if sink is not None:
        with jax.named_scope("attn.sink"):
            scores.append(jnp.broadcast_to(
                sink.astype(jnp.float32)[None, :, :, None, None],
                scores[0].shape[:-1] + (1,)))
    s = jnp.concatenate(scores, axis=-1)
    probs = jax.nn.softmax(s, axis=-1).astype(dtype)
    att, at = 0, 0
    for _, values, valid in segments:
        n = valid.shape[-1]
        att = att + jnp.einsum("bgrts,bsgd->btgrd", probs[..., at:at + n],
                               values)
        at += n
    return att


def _weigh(p, values, dtype):
    return jnp.einsum("bgrts,bsgd->btgrd", p.astype(dtype), values,
                      preferred_element_type=jnp.float32)


def _per_row(a):  # (G, HK, R, T) -> (G, T, HK, R, 1)
    return jnp.transpose(a, (0, 3, 1, 2))[..., None]


def _own_rows(q, k, v, own_valid, scale, dtype):
    """The running softmax (max, sum, accumulator: float32) started on
    the program's own rows, of which every query row sees at least one:
    a masked slot's weight ``exp(-1e30 - m)`` is 0 from here on."""
    s = _scores(q, k, own_valid, scale)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    return m, jnp.sum(p, axis=-1), _weigh(p, v, dtype)


def _fold(carry, s, values, dtype):
    """Scores s (G, HK, R, T, S) and their values folded into a running
    softmax ``(m, l, acc)``."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    return (m_new, alpha * l + jnp.sum(p, axis=-1),
            _per_row(alpha) * acc + _weigh(p, values, dtype))


def _from_sink(q, k, v, own_valid, scale, dtype, sink):
    """The running softmax started from the sink's column, ``(m, l) =
    (sink, 1)`` with nothing accumulated (the column has no value), then
    the program's own rows."""
    G, T, HK, R, _ = q.shape
    with jax.named_scope("attn.sink"):
        m = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, :, None],
                             (G, HK, R, T))
        carry = (m, jnp.ones_like(m),
                 jnp.zeros((G, T, HK, R, v.shape[-1]), jnp.float32))
    return _fold(carry, _scores(q, k, own_valid, scale), v, dtype)


def _tile_step(ctx, layer, scale, dtype, q, tables, lengths):
    """``step(t, carry)``: tile t of these lanes' context folded into
    their running softmax."""
    per = ctx.layout.tile_pages
    tile = per * ctx.layout.block_size

    def step(t, carry):
        kc, vc = ctx.read(layer, jax.lax.dynamic_slice_in_dim(
            tables, t * per, per, axis=1))
        slot = t * tile + jnp.arange(tile)
        valid = jnp.broadcast_to(
            slot[None, None, :] < lengths[:, None, None],
            (q.shape[0], q.shape[1], tile))
        return _fold(carry, _scores(q, kc, valid, scale), vc, dtype)

    return step


def _window_tile(ctx, layer, scale, dtype, q, carry):
    """The one tile of a window kind: for lane b the `window_pages` pages
    from the page that holds slot ``lengths[b] - window + 1`` on, the
    first a row of the program can see; row t, at position ``lengths[b]
    + t``, sees the slots above ``position - window`` of them."""
    lay = ctx.layout
    T = q.shape[1]
    first = jnp.maximum(ctx.lengths - (lay.window - 1), 0) // lay.block_size
    kc, vc = ctx.read(layer, jnp.take_along_axis(
        ctx.tables, first[:, None] + jnp.arange(lay.window_pages)[None],
        axis=1))
    slot = first[:, None] * lay.block_size \
        + jnp.arange(lay.window_pages * lay.block_size)[None]  # (B, S)
    position = ctx.lengths[:, None] + jnp.arange(T)[None]  # (B, T)
    valid = (slot[:, None, :] < ctx.lengths[:, None, None]) \
        & (slot[:, None, :] > position[:, :, None] - lay.window)
    m, l, acc = _fold(carry, _scores(q, kc, valid, scale), vc, dtype)
    return (acc / _per_row(l)).astype(dtype)


def _read_by_kernel(q, k, v, own_valid, k_pages, v_pages, tables, lengths,
                    layer, **static):
    from ray_tpu.ops.paged_attention import paged_attention

    return paged_attention(q, k, v, own_valid, k_pages, v_pages, tables,
                           lengths, layer=layer, **static)


_read_by_kernel_once = jax.jit(
    _read_by_kernel, static_argnames=("layout", "dtype", "scale",
                                      "interpret"))


def _by_kernel(q, k, v, own_valid, ctx, layer, dtype, scale=None,
               once: bool = False):
    """The whole read handed to the Pallas kernel (interpreted off a
    TPU: the tests). `once`: through one jitted function, the layer a
    traced operand, so that a family that unrolls its layers traces and
    lowers the kernel once a program and not once a layer."""
    read = _read_by_kernel_once if once else _read_by_kernel
    with jax.named_scope("attn.ctx_read"):
        return read(q, k, v, own_valid, ctx.k_pages, ctx.v_pages, ctx.tables,
                    ctx.lengths, layer, layout=ctx.layout, dtype=dtype,
                    scale=scale, interpret=jax.default_backend() != "tpu")


def attend_cached(q, k, v, own_valid, ctx: CachedContext, layer, dtype,
                  sink=None):
    """q (B, T, HK, R, D) attends, under one softmax scaled by
    ``1 / sqrt(D)``, to lane b's cached rows ``[0, ctx.lengths[b])`` of
    layer `layer` and to the program's own rows k (B, T, HK, D), v (B, T,
    HK, Dv) where `own_valid` (B, T, T) allows -> (B, T, HK, R, Dv) in
    `dtype`. In a window kind (`ctx.layout.window`) row t, at position
    ``ctx.lengths[b] + t``, sees of both only the rows above ``position -
    window``. `sink` (HK, R): a column of the softmax with no value.

    A program of few rows a lane hands the whole of it to the kernel
    where `reads_by_kernel` allows. Otherwise,
    tile by tile: tile t is read for the rows of every group that reaches
    it, which are the first rows of the program, whatever the lanes'
    order (no group behind a group reaches further than `ctx.reach` says
    of it). One loop a group, last group first: it runs the tiles that its
    group reaches and the groups behind it did not, on all rows up to its
    group's, and leaves its group's rows finished. A window kind reads
    its one tile a lane instead (`_window_tile`)."""
    if reads_by_kernel(ctx.layout, q.shape[1], sink is not None):
        return _by_kernel(q, k, v, own_valid, ctx, layer, dtype)
    B, G = q.shape[0], ctx.group
    scale = 1.0 / (q.shape[-1] ** 0.5)
    window = ctx.layout.window
    if window is not None:
        T = q.shape[1]
        own_valid = own_valid & (
            jnp.arange(T)[:, None] - jnp.arange(T)[None, :] < window)
    if sink is None:
        carry = _own_rows(q, k, v, own_valid, scale, dtype)
    else:
        carry = _from_sink(q, k, v, own_valid, scale, dtype, sink)
    with jax.named_scope("attn.ctx_read"):
        if window is not None:
            return _window_tile(ctx, layer, scale, dtype, q, carry)
        done, at = [], 0
        for rows in range(B, 0, -G):  # the groups' ends, last group first
            upto = ctx.reach[rows // G - 1]
            m, l, acc = jax.lax.fori_loop(
                at, upto, _tile_step(ctx, layer, scale, dtype, q[:rows],
                                     ctx.tables[:rows], ctx.lengths[:rows]),
                carry)
            done.append(
                (acc[rows - G:] / _per_row(l[rows - G:])).astype(dtype))
            carry = (m[:rows - G], l[:rows - G], acc[:rows - G])
            at = upto
        return jnp.concatenate(done[::-1])


# --------------------------------------------------------------------------
# A kind that selects: latent rows chosen by an indexer (`KVLayout.select`;
# `ctx.k_pages` holds the latent rows, `ctx.v_pages` the indexer's keys).

MASKED = -1e30  # an index score no slot that can be seen has


def index_scores(qi, ki, w, valid):
    """The indexer's scores of query rows qi (G, T, HI, Di) with head
    weights w (G, T, HI) float32 against keys ki (G, S, Di): ``sum_j w[t,
    j] relu(qi[t, j] . ki[s])`` -> (G, T, S) float32, `MASKED` where
    `valid` (G, T, S) is false."""
    s = jnp.einsum("bthd,bsd->bths", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.where(valid, jnp.einsum("bths,bth->bts", jax.nn.relu(s), w),
                     MASKED)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 != 0, ~b, b | jnp.uint32(0x80000000))


def _kth_largest(keys, k: int):
    """The k-th largest of each row of keys (..., S) uint32, S > k: the
    largest value that k of the row reach, found a bit at a time, each a
    masked count over the row. (`lax.top_k` sorts: 2.6 ms against 0.29
    for 256 rows of 17,664 on the v5e, PERF.md, PR 40.)"""
    def grow(i, kth):
        bit = jax.lax.shift_left(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= (kth | bit), axis=-1, keepdims=True)
        return jnp.where(reach >= k, kth | bit, kth)

    return jax.lax.fori_loop(
        0, 32, grow, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def select_mask(scores, k: int):
    """Which slots each row attends to: the k of largest `scores` (..., S)
    among those not `MASKED` (all of them while there are no more than
    k), of equal scores the earliest, exactly. Nothing is searched for
    where no row has more than k slots to choose from."""
    seen = scores > MASKED / 2
    if scores.shape[-1] <= k:
        return seen

    def cut(x):
        keys = _ordered_bits(x)
        kth = _kth_largest(keys, k)
        above = keys > kth
        tied = keys == kth
        spare = k - jnp.sum(above, axis=-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= spare))

    return seen & jax.lax.cond(
        jnp.max(jnp.sum(seen, axis=-1)) > k, cut,
        lambda x: jnp.ones(x.shape, bool), scores)


def _cached_index_scores(ctx, layer, qi, w):
    """The index scores of the program's rows against their lanes' cached
    indexer keys -> (B, T, slots the padded tables hold) float32, `MASKED`
    past a lane's length: tile t computed once, for the rows of every
    group that reaches it (`attend_cached`'s loops)."""
    lay, B, G = ctx.layout, qi.shape[0], ctx.group
    per = lay.tile_pages
    tile = per * lay.block_size
    slots = ctx.tables.shape[1] * lay.block_size

    def tiles_of(rows):
        def step(t, buf):
            keys = lay.read(ctx.v_pages, layer, jax.lax.dynamic_slice_in_dim(
                ctx.tables[:rows], t * per, per, axis=1))[:, :, 0]
            s = index_scores(qi[:rows], keys, w[:rows],
                             jnp.ones((), bool))
            return jax.lax.dynamic_update_slice(buf, s, (0, 0, t * tile))
        return step

    buf = jnp.full((B, qi.shape[1], slots), MASKED, jnp.float32)
    at = 0
    for rows in range(B, 0, -G):  # the groups' ends, last group first
        upto = ctx.reach[rows // G - 1]
        buf = jax.lax.fori_loop(at, upto, tiles_of(rows), buf)
        at = upto
    return jnp.where(jnp.arange(slots)[None, None, :]
                     < ctx.lengths[:, None, None], buf, MASKED)


def attend_selected(q, latent, qi, ki, w, own_valid, ctx: CachedContext,
                    layer, dtype, *, values: int, scale: float,
                    with_choice: bool = False):
    """Absorbed latent attention under the indexer's choice. q (B, T, H,
    row) are the heads' queries on a latent row, `latent` (B, T, row) the
    program's own rows, whose first `values` lanes are also the values;
    qi (B, T, HI, Di), ki (B, T, Di), w (B, T, HI) the indexer's queries,
    own keys and head weights. Row t of lane b, at position
    ``ctx.lengths[b] + t``, attends under one softmax (scores times
    `scale`) to the ``ctx.layout.select`` highest-scored of its lane's
    cached slots and the own rows `own_valid` (B, T, T) allows, taken
    together -> (B, T, H, values) in `dtype`; `with_choice`: and the
    choice (B, T, cached slots + T) bool, for the benchmark's parity.

    The lanes' latent tiles are folded as far as their groups reach
    (`ctx.reach`), the choice as their mask (`_fold_chosen`)."""
    q = q[:, :, None]  # (B, T, 1, H, row): one KV head, H query heads
    own = latent[:, :, None]  # (B, T, 1, row)
    with jax.named_scope("attn.index.score"):
        with jax.named_scope("attn.ctx_read"):
            cached = _cached_index_scores(ctx, layer, qi, w)
        scores = jnp.concatenate(
            [cached, index_scores(qi, ki, w, own_valid)], axis=-1)
    with jax.named_scope("attn.index.topk"):
        chosen = select_mask(scores, ctx.layout.select)
    with jax.named_scope("attn.mla.core"):
        att = _fold_chosen(q, own, chosen, ctx, layer, dtype, values, scale)
    return (att, chosen) if with_choice else att


def attend_latent(q, latent, own_valid, ctx: CachedContext, layer, dtype, *,
                  values: int, scale: float):
    """Absorbed latent attention over EVERY cached slot (a latent kind
    with no indexer, `KVLayout.v_head_dim` 0). q (B, T, H, row) are the
    heads' queries on a latent row, `latent` (B, T, row) the program's own
    rows, whose first `values` lanes are also the values. Row t of lane b
    attends under one softmax (scores times `scale`) to its lane's cached
    slots ``[0, ctx.lengths[b])`` and the own rows `own_valid` (B, T, T)
    allows -> (B, T, H, values) in `dtype`. A program of few rows a lane
    hands the whole of it to the kernel where `reads_by_kernel` allows,
    the latent row one KV head that every query head reads; otherwise the
    lanes' latent tiles are folded as far as their groups reach, each
    lane to its own length, one row for all heads."""
    q = q[:, :, None]  # (B, T, 1, H, row): one KV head, H query heads
    own = latent[:, :, None]  # (B, T, 1, row)
    if reads_by_kernel(ctx.layout, q.shape[1]):
        return _by_kernel(q, own, own[..., :values], own_valid, ctx, layer,
                          dtype, scale, once=True)[:, :, 0]
    tile = ctx.layout.tile_pages * ctx.layout.block_size

    def below_length(rows, t):
        slot = t * tile + jnp.arange(tile)
        return jnp.broadcast_to(
            slot[None, None, :] < ctx.lengths[:rows, None, None],
            (rows, q.shape[1], tile))

    return _fold_latent(q, own, lambda: own_valid, below_length, ctx, layer,
                        dtype, values, scale)


def _fold_chosen(q, own, chosen, ctx, layer, dtype, values: int, scale):
    """The lanes' latent tiles folded under the choice as their mask. q
    (B, T, 1, H, row), own (B, T, 1, row), chosen (B, T, cached slots +
    T) -> (B, T, H, values)."""
    n_cached = chosen.shape[-1] - q.shape[1]
    tile = ctx.layout.tile_pages * ctx.layout.block_size
    # a row whose own rows were all passed over starts from weights that
    # the first chosen cached slot scales to nothing (`_fold`)
    return _fold_latent(
        q, own, lambda: chosen[..., n_cached:],
        lambda rows, t: jax.lax.dynamic_slice_in_dim(
            chosen[:rows], t * tile, tile, axis=2),
        ctx, layer, dtype, values, scale)


def _fold_latent(q, own, own_valid, tile_valid, ctx, layer, dtype,
                 values: int, scale):
    """The lanes' latent tiles folded into a softmax started on the own
    rows, by `attend_cached`'s loops: tile t read once for the rows of
    every group that reaches it, ``tile_valid(rows, t)`` (rows, T, tile)
    its mask for the program's first `rows` rows, ``own_valid()`` (B, T,
    T) the own rows'."""
    lay, B, G = ctx.layout, q.shape[0], ctx.group
    carry = _own_rows(q, own, own[..., :values], own_valid(), scale, dtype)
    per = lay.tile_pages

    def tiles_of(rows):
        def step(t, carry):
            latent = lay.read(ctx.k_pages, layer, jax.lax.dynamic_slice_in_dim(
                ctx.tables[:rows], t * per, per, axis=1))
            valid = tile_valid(rows, t)
            return _fold(carry, _scores(q[:rows], latent, valid, scale),
                         latent[..., :values], dtype)
        return step

    with jax.named_scope("attn.ctx_read"):
        done, at = [], 0
        for rows in range(B, 0, -G):  # the groups' ends, last group first
            upto = ctx.reach[rows // G - 1]
            m, l, acc = jax.lax.fori_loop(at, upto, tiles_of(rows), carry)
            done.append((acc[rows - G:] / _per_row(l[rows - G:]))
                        .astype(dtype)[:, :, 0])
            carry = (m[:rows - G], l[:rows - G], acc[:rows - G])
            at = upto
        return jnp.concatenate(done[::-1])
