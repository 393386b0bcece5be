"""Block KV-cache pool: fixed-size pages, refcounts, and a
content-addressed prefix index (automatic prefix caching).

The device arrays themselves live in the ModelRunner: one K and one V
pool a KIND of layer that has keys and values. Most families have one
kind (every such layer alike); a family that mixes full and window
attention has two (`KVKind`), each with its own head count, its own K and
V row widths and, for a window kind, the window. A **latent** kind
(`KVKind.select`: latent attention under a learned indexer) keeps no
values: a token's first row is its latent row, key and value of every
head at once, and its second row, in the pool where another kind keeps V,
is the indexer's key, both under one block table and one page count; a
latent kind with no indexer (`v_head_dim` 0: every earlier slot is read)
has the first row alone, one pool a layer.
This module owns the
*bookkeeping* (`BlockPool`: which physical pages are free, which pages
hold which token content; `KVPools`: a model's pools, one a kind, and
what is counted of them) and the pools' *layout* (`KVLayout`: their shape
and sharding, how a layer's context is read through block tables and how
new rows are written). Nothing else in the package spells a pool's shape.

A sequence has a block table a kind (scheduler.py). A full kind's table
grows with the sequence. A window kind's holds only the pages that some
later row can still see: a page wholly behind the window of the next row
to be planned goes back to its pool's free list while the sequence runs
(its table entry becomes the null page), so a lane costs at most
`KVLayout.lane_pages` pages of that pool however long it gets.

A family with recurrent (state-space) layers has a second kind of state
beside the pages, fixed in size a lane: `StateLayout` (its buffers),
`StateView` (how a program reads and writes them) and `StateSlots` (its
counters), further down.

Page 0 is reserved as a **null sink**: it is never handed out, padded
lanes of a bucketed batch point their tables at it, and so does every
group of `block_size` positions of a prompt's or a chunk's program that
is all padding. Gathers through a padded table therefore always hit a
legal page, and the attention mask (not the allocator) is what keeps
garbage out of the softmax.

A prompt's and a chunk's programs store their rows a page at a time
(`KVLayout.write_pages`: their rows start on a page's edge and their
buckets are whole pages), so the padded rows of the LAST page that holds
a valid row land in that page, the sequence's own, behind its frontier,
where a speculative verify's rejected rows land too. That is safe
because, each held by a test of tests/test_kv_page_write.py or
tests/test_kv_pool_layout.py:

- every read of a lane's context is masked by the lane's length, so a
  slot at or behind the frontier is never seen;
- the row that belongs in such a slot is written (by the next chunk, by
  the decode step at that position, by a verify dispatch from the
  frontier on) before any program reads the slot;
- only FULL pages are registered with the prefix index, and a page is
  full only once real rows have overwritten every slot of it, so no other
  sequence ever shares a page with padding in it;
- a chunk owns every page its rows fall in while it runs, in a window
  kind too (`lane_pages`): a page is given back only behind the window.

Several all-padding groups share page 0 in one scatter; which of them
lands there is nobody's business, as it was with padded rows.

Prefix caching (reference shape: vLLM's automatic prefix caching):

- a **full** page's content is identified by a *hash chain* over token
  ids — ``h_k = H(h_{k-1}, tokens[k*bs:(k+1)*bs])`` — so equal hashes
  imply equal token *prefixes*, not just equal page contents;
- every allocated page is **refcounted**; sequences whose prompts share
  a prefix share the physical pages (each holds one ref);
- releasing the last ref of a *registered* page does not free it — the
  page parks in an LRU of evictable pages, still indexed by hash, so a
  later request (or a preempted sequence re-admitting) can revive it
  with `match_prefix`. `alloc` takes truly-free pages first and only
  then evicts LRU refcount-0 pages (oldest first).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
from collections import OrderedDict
from typing import Any, Iterable, NamedTuple, Sequence

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


# Elements of K in one tile of a lane's cached context, for each layer
# of the pool's kind: the unit in which the dense serve
# programs read it (`KVLayout.tile_pages`, ops/context_attention.py). A
# step of the read costs every such layer some twenty small operations
# (1.5 us a step on the v5e, and an event each in a device trace) beside
# the tile's own bytes, so a deeper stack takes larger tiles.
TILE_ELEMENTS_A_LAYER = 32 * 1024
# Slots in one tile of a latent kind that is read whole (no indexer): a
# chunk's 256 rows fold 19 GFLOP a loop step at a 640-lane row, against
# some 30 us of small operations a step (`tile_pages`; PERF.md, PR 51).
# It sizes a chunk's tile alone on the chip: a decode step reads with the
# kernel, by `paged_attention.pages_a_step` (PR 52), and takes this tile
# only on the loops (the CPU, a pool split over `tensor`)
DENSE_LATENT_TILE_SLOTS = 1024


class CacheExhausted(Exception):
    """Raised by alloc() when the pool cannot satisfy a request; the
    scheduler turns this into preemption, not an error."""


def hash_page(prev_hash: int, tokens: Sequence[int]) -> int:
    """Content hash of one full page given the previous page's chain
    hash (0 for the first page). Chained, so a page hash commits to the
    entire token prefix ending at that page; stable across processes
    (blake2b, not Python's salted hash) so the same function can key
    replica affinity routing."""
    h = hashlib.blake2b(digest_size=8)
    h.update(prev_hash.to_bytes(8, "little", signed=False))
    for t in tokens:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return int.from_bytes(h.digest(), "little")


def chain_hashes(tokens: Sequence[int], block_size: int,
                 n_pages: int) -> list[int]:
    """Hash chain over the first `n_pages` full pages of `tokens`."""
    out: list[int] = []
    prev = 0
    for k in range(n_pages):
        prev = hash_page(prev, tokens[k * block_size:(k + 1) * block_size])
        out.append(prev)
    return out


def window_lane_pages(window: int, rows: int, block_size: int) -> int:
    """Most pages of a window kind a lane holds while a program of
    `rows` rows is planned: the window behind its first row and the rows
    themselves, wherever the pages' edges fall."""
    return (window + rows - 2) // block_size + 2


class KVKind(NamedTuple):
    """One kind of layer that has keys and values, as a family's adapter
    describes it (`ModelAdapter.kv_kinds`): how many layers, their KV
    heads, the width of a K head and of a V head, and the window: a row
    sees itself and the `window - 1` rows before it, or every earlier row
    (None). `select` makes it a latent kind: one head, `head_dim` the
    latent row's lanes, `v_head_dim` the indexer key's, and a row sees of
    the earlier rows and itself only the `select` its indexer scores
    highest. A `v_head_dim` of 0 is a latent kind with NO indexer: one
    pool a layer (the second is empty), the values the leading lanes of
    the one row, and a row sees every earlier row."""

    name: str
    layers: int
    n_kv_head: int
    head_dim: int
    v_head_dim: int
    window: int | None = None
    select: int | None = None

    @property
    def latent(self) -> bool:
        return self.select is not None or self.v_head_dim == 0


@dataclasses.dataclass(frozen=True)
class KVLayout:
    """How the K and the V page pool of one kind of layer lie on the
    device, and the only code that indexes them.

    The K pool is ``(kv_layers, num_blocks, block_size, n_kv_head *
    head_dim)`` and the V pool the same with `v_head_dim` (as wide as K's
    unless the family says otherwise), `kv_layers` being the layers of
    this kind (every layer of a dense stack, 2 of 18 in nemotron_h's cut,
    2 full and 5 window layers in mimo_v2's): one token's heads side by
    side in one lane-dense row (1280 lanes at gpt2-large), so the bf16
    tile ``(8, 128)(2, 1)`` over ``(block_size, row)`` pads nothing and
    XLA keeps the array row-major. Three things together keep every serve
    program from copying a pool whole (each alone leaves the copies;
    PERF.md, PR 26): this row, a context read per layer inside the layer
    scan (`read`), and a scatter whose every indexed dimension leads:
    all three of them where single rows are stored (`write`: decode,
    verify), layer and page where whole pages are (`write_pages`: a
    prompt's and a chunk's programs, a sixteenth of the indices at pages
    of 16). XLA does either in place on the donated buffer.

    A latent kind (`select`) has the same two pools, read and written the
    same way, holding other rows: the first the latent rows (at GLM-5 512
    + 64 lanes and 64 of padding, which the family adds: 4.5 lane tiles
    make XLA:TPU copy the pool around every program, 5 do not), the second
    the indexer's keys (128 lanes). No values are stored: the attention
    takes them from the latent row. A latent kind without an indexer
    (`v_head_dim` 0) has the first pool alone: the second has no lanes,
    takes no memory, and what is stored in it is nothing.
    """

    kv_layers: int
    num_blocks: int
    block_size: int
    n_kv_head: int
    head_dim: int
    v_head_dim: int | None = None  # None: as wide as a K head; 0: no V row
    window: int | None = None  # None: a row sees every earlier row
    select: int | None = None  # a latent kind: the slots a row's indexer picks

    @classmethod
    def of(cls, kind: KVKind, num_blocks: int, block_size: int):
        return cls(kind.layers, num_blocks, block_size, kind.n_kv_head,
                   kind.head_dim, kind.v_head_dim, kind.window, kind.select)

    @property
    def row(self) -> int:
        """Lanes of one token's K row: head ``h`` is ``[h * head_dim,
        (h + 1) * head_dim)``."""
        return self.n_kv_head * self.head_dim

    @property
    def v_row(self) -> int:
        return self.n_kv_head * (self.head_dim if self.v_head_dim is None
                                 else self.v_head_dim)

    @property
    def latent(self) -> bool:
        """A kind whose first row is key and value of every query head:
        under an indexer (`select`) or read whole (`v_head_dim` 0)."""
        return self.select is not None or self.v_head_dim == 0

    @property
    def shape(self) -> tuple[int, int, int, int]:
        """Of the K pool (and of the V pool where the rows are alike)."""
        return (self.kv_layers, self.num_blocks, self.block_size, self.row)

    @property
    def v_shape(self) -> tuple[int, int, int, int]:
        return self.shape[:3] + (self.v_row,)

    @property
    def tile_pages(self) -> int:
        """Pages in one tile of a lane's context: the largest power of
        two whose K rows hold at most `TILE_ELEMENTS_A_LAYER` a layer of
        the pool (32 pages, 512 slots, at gpt2-large's 36 layers of 1280;
        8 pages at 8 layers of OLMoE's 2048-wide row; 16 pages at the
        nemotron_h cut's 2 layers of 256; 4 pages at mimo_v2's 2 full
        layers of 768). A latent kind's tile is sized by the indexer
        key's row, the one read to every lane's length (64 pages, 1,024
        slots, at GLM-5's 5 layers of 128); without an indexer its tile
        is `DENSE_LATENT_TILE_SLOTS` slots whatever the layers: every
        query head reads the one row, so a tile's products are heads
        times a full kind's for the same bytes."""
        if self.latent and self.select is None:
            pages = max(1, DENSE_LATENT_TILE_SLOTS // self.block_size)
            return 1 << (pages.bit_length() - 1)
        row = self.row if self.select is None else self.v_row
        pages = max(1, TILE_ELEMENTS_A_LAYER * self.kv_layers
                    // (self.block_size * row))
        return 1 << (pages.bit_length() - 1)

    @property
    def window_pages(self) -> int:
        """Pages that hold every cached slot a program's rows can see in
        a window kind, wherever the window starts in its first page: the
        ``window - 1`` slots before the program's first row."""
        return max(0, self.window - 2) // self.block_size + 2

    def lane_pages(self, rows: int) -> int:
        return window_lane_pages(self.window, rows, self.block_size)

    def shard_ways(self, tensor_ways: int) -> int:
        """Over how many `tensor` shards the rows split: whole heads
        only (contiguous head blocks), else the pools are replicated."""
        if tensor_ways > 1 and self.n_kv_head % tensor_ways == 0:
            return tensor_ways
        return 1

    def token_bytes(self, dtype_bytes: int) -> dict:
        """Bytes a token takes in this kind's layers, by sort of row."""
        k, v = ("latent", "index") if self.latent else ("k", "v")
        return {k: self.kv_layers * self.row * dtype_bytes,
                v: self.kv_layers * self.v_row * dtype_bytes}

    def block_bytes(self, dtype_bytes: int, tensor_ways: int = 1) -> int:
        """Bytes one page takes on one device, K and V together."""
        return (self.kv_layers * self.block_size * (self.row + self.v_row)
                * dtype_bytes // self.shard_ways(tensor_ways))

    def spec(self, mesh) -> PartitionSpec:
        ways = dict(mesh.shape).get("tensor", 1)
        if self.shard_ways(ways) > 1:
            return PartitionSpec(None, None, None, "tensor")
        return PartitionSpec()

    def zeros(self, dtype, mesh=None) -> tuple:
        """An empty (K pool, V pool), placed by `spec` when there is a
        mesh."""
        device = (NamedSharding(mesh, self.spec(mesh))
                  if mesh is not None else None)
        return (jnp.zeros(self.shape, dtype, device=device),
                jnp.zeros(self.v_shape, dtype, device=device))

    def read(self, pages, layer, tables):
        """Layer `layer`'s context for block tables ``(..., n)``, from
        the K or the V pool: ``(..., n * block_size, n_kv_head, head
        width)``, slot ``c`` being position ``c`` of the table's
        sequence. `layer` may be traced: call it inside the layer scan,
        so one layer's pages are gathered at a time (gathering every
        layer at once makes XLA transpose the result)."""
        # one gather indexed by (layer, page): `pages[layer][tables]`
        # first copies the layer out of the pool (21 MB a layer at
        # gpt2-large; a tenth of both serve cells, PERF.md PR 26)
        ctx = pages[layer, tables]  # (..., n, block_size, row)
        return ctx.reshape(*tables.shape[:-1],
                           tables.shape[-1] * self.block_size,
                           self.n_kv_head,
                           pages.shape[-1] // self.n_kv_head)

    def write(self, pages, block_ids, offsets, rows):
        """Store ``rows (kv_layers, N, n_kv_head, head width)`` at slots
        ``(block_ids[i], offsets[i])`` of every layer of the K or the V
        pool. Every scatter dimension leads and the row is the only
        window: a leading ``:`` would make XLA transpose the pool around
        the scatter."""
        kv_layers, n = rows.shape[:2]
        layers = jnp.arange(kv_layers)[:, None]
        return pages.at[layers, block_ids[None, :], offsets[None, :]].set(
            rows.reshape(kv_layers, n, pages.shape[-1]))

    def whole_pages(self, n: int) -> bool:
        """Whether `n` rows that start on a page's edge are whole pages
        (`write_pages` then stores them a page at a time)."""
        return n % self.block_size == 0

    def group_pages(self, n: int) -> int:
        """Pages that `n` rows starting on a page's edge fall in: the ids
        `write_pages` takes."""
        return -(-n // self.block_size)

    def write_pages(self, pages, page_ids, rows):
        """Store ``rows (kv_layers, N, n_kv_head, head width)``, which
        start on a page's edge, in every layer of the K or the V pool:
        group ``g`` of `block_size` rows in page ``page_ids[g]``
        (`group_pages(N)` ids). Whole pages (`whole_pages(N)`: every
        bucket of a prompt's or a chunk's program at the defaults) are
        one scatter on (layer, page) whose window is a whole ``(block_size,
        row)`` page: a sixteenth of the indices of `write` at pages of 16,
        and the count of indices is what a scatter costs (PERF.md, PR 37).
        Anything else is stored row by row, to the same slots."""
        kv_layers, n = rows.shape[:2]
        if not self.whole_pages(n):
            at = jnp.arange(n)
            return self.write(pages, page_ids[at // self.block_size],
                              at % self.block_size, rows)
        layers = jnp.arange(kv_layers)[:, None]
        return pages.at[layers, page_ids[None, :]].set(
            rows.reshape(kv_layers, n // self.block_size, self.block_size,
                         pages.shape[-1]))

    def page_block(self) -> tuple:
        """BlockSpec shape of one page for a Pallas kernel: tile-aligned
        ``(block_size, row)``, layer and page squeezed."""
        return (None, None, self.block_size, self.row)

    def page_index(self, layer, page) -> tuple:
        """Block index of page `page` of layer `layer` for `page_block`."""
        return (layer, page, 0, 0)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """The second kind of cached state: what a recurrent (state-space)
    layer carries from one token to the next. It does not grow with the
    sequence, so it is not paged: every lane slot (`Sequence.slot`, the
    index the runner's `slot_tokens` uses too) owns one row of every part,
    in every layer that has state. A part's buffer is ``(layers, slots,
    *shape)`` in its own dtype (a Mamba-2 layer: the last 3 conv inputs in
    bf16, a part each, and the SSM state in float32).

    Like `KVLayout` this is the only code that spells the buffers' shape;
    the traced reads and writes are `StateView`'s."""

    layers: int  # layers that HAVE recurrent state
    slots: int
    # (name, shape a lane and layer, dtype), one entry a part
    parts: tuple[tuple[str, tuple[int, ...], Any], ...]

    def shape(self, part: tuple) -> tuple[int, ...]:
        return (self.layers, self.slots) + tuple(part[1])

    @property
    def slot_bytes(self) -> int:
        """Bytes one lane's state takes, all layers and parts."""
        return sum(self.layers * math.prod(shape) * jnp.dtype(dt).itemsize
                   for _, shape, dt in self.parts)

    @property
    def nbytes(self) -> int:
        return self.slots * self.slot_bytes

    def zeros(self, mesh=None) -> dict:
        """Empty buffers, replicated when there is a mesh."""
        device = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)
        return {part[0]: jnp.zeros(self.shape(part), part[2], device=device)
                for part in self.parts}


class StateView:
    """A program's handle on the state buffers while it is traced: the
    model's layers read and write through it, the runner takes `buffers`
    back when the forward returns. Every write replaces the buffer it
    touches by an update of itself at static layer (and dynamic slot)
    indices, which XLA does in place on the donated buffer.

    Two shapes of program:

    - **one lane** (a prompt or a chunk of it; `slots` a scalar): `lane`
      gives that slot's rows, zeros when the program runs the sequence's
      first rows (`fresh`: a reused slot starts from zero, whatever its
      last owner left), `set_lane` stores them. A negative slot (warm-up)
      stores nothing;
    - **every slot** (decode; `slots` (Sb,), -1 for a padded lane): the
      state is updated where it lies, all slots of a layer in one
      elementwise pass (`all` / `set_all`), so the lanes' inputs go to
      slot order (`to_slots`: zeros for a slot no lane of the step owns,
      `owned` False there, which the layer must turn into "written back
      as read") and its outputs come back (`from_slots`). Both are
      gathers: which lane owns a slot is worked out once a program."""

    def __init__(self, layout: StateLayout, buffers: dict, slots,
                 fresh=None):
        self.layout = layout
        self.buffers = dict(buffers)
        self.slots = slots
        self.fresh = fresh
        if jnp.ndim(slots) == 1:
            # lane_of[s]: the lane of this step that owns slot s, or -1
            # (one 32-wide scatter a program; a padded lane's -1 is
            # dropped as out of range)
            self.lane_of = jnp.full((layout.slots,), -1, jnp.int32).at[
                jnp.where(slots >= 0, slots, layout.slots)].set(
                    jnp.arange(slots.shape[0], dtype=jnp.int32), mode="drop")
            self.owned = self.lane_of >= 0

    # ------------------------------------------------------------ one lane

    def lane(self, layer: int) -> dict:
        slot = jnp.maximum(self.slots, 0)
        return {name: jnp.where(self.fresh, jnp.zeros((), buf.dtype),
                                buf[layer, slot])
                for name, buf in self.buffers.items()}

    def set_lane(self, layer: int, rows: dict) -> None:
        slot = jnp.maximum(self.slots, 0)
        for name, new in rows.items():
            buf = self.buffers[name]
            new = jnp.where(self.slots >= 0, new.astype(buf.dtype),
                            buf[layer, slot])
            self.buffers[name] = buf.at[layer, slot].set(new)

    # ---------------------------------------------------------- every slot

    def to_slots(self, x):
        """x (Sb, ...) in lane order -> (slots, ...) in slot order, zeros
        where no lane of the step owns the slot."""
        rows = x[jnp.maximum(self.lane_of, 0)]
        return jnp.where(
            self.owned.reshape((-1,) + (1,) * (x.ndim - 1)), rows,
            jnp.zeros((), x.dtype))

    def from_slots(self, y):
        """y (slots, ...) -> (Sb, ...); a padded lane reads slot 0."""
        return y[jnp.maximum(self.slots, 0)]

    def all(self, layer: int) -> dict:
        return {name: buf[layer] for name, buf in self.buffers.items()}

    def set_all(self, layer: int, name: str, new) -> None:
        buf = self.buffers[name]
        self.buffers[name] = buf.at[layer].set(new.astype(buf.dtype))

    def in_place(self, name: str, update):
        """Hand part `name`'s buffer, every layer of it as it lies, to
        `update` (a kernel that aliases it to its first result) and take
        it back: ``update(buffer) -> (buffer, out)``; gives `out`."""
        self.buffers[name], out = update(self.buffers[name])
        return out


class StateSlots:
    """The bookkeeping half of the recurrent state (the buffers live in
    the ModelRunner, as the pages do): how much there is, and how often a
    slot was started from zero. Every admission of a stateful family,
    a recompute after preemption too, starts its slot from zero, because
    a prefix hit would hand over pages of K and V but no state: where
    prefix reuse was asked for (`prefix_declined`), each reset is also a
    prefix match not attempted. Beside the resets (prefill programs that
    started a slot fresh): the prefill programs that started from the
    state an earlier chunk left in the slot (`carried`), and the decode
    programs by their rows (the lanes' bucket) with the slots their lanes
    owned, the others written back as read, and those of them whose
    one-step recurrence went through the Pallas kernel (`kernel_steps`:
    the runner says which path its programs take, `ops/ssm_step.py`).
    Written by the engine's one stepping thread."""

    def __init__(self, layout: StateLayout, prefix_declined: bool):
        self.layout = layout
        self.prefix_declined = prefix_declined
        self.resets = 0
        self.carried = 0
        self.decode_steps: dict[int, int] = {}  # rows of the program: steps
        self.decode_lanes = 0  # slots owned, summed over the steps
        self.kernel_steps = 0

    def note_decode(self, rows: int, lanes: int, kernel: bool) -> None:
        self.decode_steps[rows] = self.decode_steps.get(rows, 0) + 1
        self.decode_lanes += lanes
        self.kernel_steps += kernel

    def stats(self) -> dict:
        lay = self.layout
        return {"slots": lay.slots, "layers": lay.layers,
                "bytes": lay.nbytes, "slot_bytes": lay.slot_bytes,
                "resets": self.resets, "carried": self.carried,
                "decode_steps": {str(rows): n for rows, n in
                                 sorted(self.decode_steps.items())},
                "decode_lanes": self.decode_lanes,
                "kernel_steps": self.kernel_steps,
                "prefix_declined": self.prefix_declined}


class BlockPool:
    """Refcounted allocator over `num_blocks` physical KV pages with a
    hash -> page prefix index.

    Thread-safe: the engine's step loop allocates while request threads
    release on abort. Lock order: `_lock` is a LEAF lock — no callback
    or foreign lock is ever taken while holding it.
    """

    def __init__(self, num_blocks: int, block_size: int, *,
                 enable_prefix_cache: bool = True):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is the null sink)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_cache = enable_prefix_cache
        self._lock = threading.Lock()
        # page 0 reserved; LIFO free list keeps hot pages hot
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))  # guarded_by(_lock)
        # allocated pages only; a page leaves this map when its count
        # drops to zero (to _free or to _lru)
        self._refcount: dict[int, int] = {}  # guarded_by(_lock)
        # content index over REGISTERED pages (full pages whose KV is
        # completely written): hash -> page and its inverse
        self._page_of: dict[int, int] = {}  # guarded_by(_lock)
        self._hash_of: dict[int, int] = {}  # guarded_by(_lock)
        # refcount-0 registered pages, oldest-first (eviction order)
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # guarded_by(_lock)
        # monotonic stat, read through `Scheduler.depth()` (hit/miss
        # accounting lives in the scheduler: only an admission that
        # actually goes through should count)
        self.evictions = 0  # guarded_by(_lock)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    def num_free(self) -> int:
        """Allocatable pages: truly free + evictable (refcount-0 LRU)."""
        with self._lock:
            return len(self._free) + len(self._lru)

    def num_used(self) -> int:
        return self.usable_blocks - self.num_free()

    def num_cached(self) -> int:
        """Refcount-0 pages retained only for prefix reuse."""
        with self._lock:
            return len(self._lru)

    def utilization(self) -> float:
        return self.num_used() / max(1, self.usable_blocks)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Pages needed to hold positions 0..n_tokens-1."""
        return (n_tokens + self.block_size - 1) // self.block_size

    def can_alloc(self, n: int) -> bool:
        with self._lock:
            return len(self._free) + len(self._lru) >= n

    # ------------------------------------------------------------- alloc

    def alloc(self, n: int) -> list[int]:
        """Pop `n` pages or raise CacheExhausted (all-or-nothing).
        Returned pages carry refcount 1 and no content registration."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        with self._lock:
            if len(self._free) + len(self._lru) < n:
                raise CacheExhausted(
                    f"need {n} blocks, "
                    f"{len(self._free) + len(self._lru)} free")
            take = min(n, len(self._free))
            out = self._free[len(self._free) - take:] if take else []
            del self._free[len(self._free) - take:]
            while len(out) < n:  # evict coldest cached pages
                page, _ = self._lru.popitem(last=False)
                self._drop_registration_locked(page)
                self.evictions += 1
                out.append(page)
            for b in out:
                self._refcount[b] = 1
            return out

    def _drop_registration_locked(self, page: int) -> None:
        """Caller holds self._lock."""
        h = self._hash_of.pop(page, None)
        if h is not None and self._page_of.get(h) == page:
            del self._page_of[h]

    # ------------------------------------------------------------ release

    def free(self, blocks: Iterable[int]) -> None:
        """Drop one reference per listed page. A page whose count hits
        zero returns to the free list — unless it is content-registered,
        in which case it parks in the LRU, revivable by match_prefix."""
        blocks = list(blocks)
        if not blocks:
            return
        with self._lock:
            for b in blocks:
                if not 0 < b < self.num_blocks:
                    raise ValueError(f"free of invalid block {b}")
                if b not in self._refcount:
                    raise ValueError(f"double free of block {b}")
            # reversed: callers pass a sequence's table in logical order,
            # so park the chain TAIL first (oldest in the LRU). Eviction
            # pops oldest-first and therefore shrinks a cached prefix
            # from its tail — the head pages stay matchable; evicting the
            # head first would orphan every page behind it.
            for b in reversed(blocks):
                self._refcount[b] -= 1
                if self._refcount[b] > 0:
                    continue
                del self._refcount[b]
                if b in self._hash_of:
                    self._lru[b] = None  # newest at the end
                    self._lru.move_to_end(b)
                else:
                    self._free.append(b)

    # ------------------------------------------------------ prefix index

    def register(self, page: int, content_hash: int) -> None:
        """Content-address a page whose KV is now completely written.
        First writer wins: if another page already claims the hash, this
        page simply stays unregistered (both copies are valid; dedup of
        in-flight duplicates is not worth a migration)."""
        if not self.enable_prefix_cache:
            return
        with self._lock:
            if page not in self._refcount:
                return  # released (abort raced the registration): skip
            if page in self._hash_of or content_hash in self._page_of:
                return
            self._hash_of[page] = content_hash
            self._page_of[content_hash] = page

    def match_prefix(self, hashes: Sequence[int]) -> list[int]:
        """Longest-prefix match: walk the hash chain, returning the run
        of consecutively indexed pages. Matched pages gain one reference
        each (revived out of the LRU if parked there) — the caller owns
        them exactly like alloc() output and releases via free()."""
        if not self.enable_prefix_cache:
            return []
        out: list[int] = []
        with self._lock:
            for i, h in enumerate(hashes):
                page = self._page_of.get(h)
                if page is None:
                    break
                if page in self._refcount:
                    self._refcount[page] += 1
                else:
                    del self._lru[page]
                    self._refcount[page] = 1
                out.append(page)
        return out

    def invalidate_prefix_cache(self) -> int:
        """Drop EVERY content registration (weight hot-swap): cached KV
        was computed under the old weights, so a post-swap admission
        matching it would silently mix weight versions inside one
        forward. Parked refcount-0 pages return to the free list;
        in-use pages stay allocated (their owners keep decoding, tagged
        stale by the engine) but lose their registration so no future
        request can match them. Returns the number of registrations
        dropped."""
        with self._lock:
            n = len(self._hash_of)
            for page in self._lru:
                self._free.append(page)
            self._lru.clear()
            self._hash_of.clear()
            self._page_of.clear()
            return n

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refcount.get(page, 0)

    def stats(self) -> dict:
        with self._lock:
            return {
                "free": len(self._free),
                "cached": len(self._lru),
                "registered": len(self._hash_of),
                "evictions": self.evictions,
            }


class KVPools:
    """A model's page pools, one `BlockPool` a kind of KV layer, and what
    is counted of them by kind (`stats`, the engine's
    ``stats()["kv"]``). The first kind is the one whose pool
    `EngineConfig.num_blocks` sizes and whose pages the prefix index
    addresses; a window kind's pool is sized off the lanes
    (`window_pool_blocks`).

    A family with a window kind takes no prefix match: a hit at a page
    boundary would be good only if the window's pages before that
    boundary were still registered, and those are released while the
    sequence runs. Where prefix reuse was asked for, each admission is
    counted as a match declined (`prefix_declined`). Counters are written
    by the engine's one stepping thread."""

    def __init__(self, kinds: Sequence[KVKind], pools: Sequence[BlockPool],
                 prefix_declined: bool = False):
        self.kinds = tuple(kinds)
        self.pools = tuple(pools)
        self.prefix_declined = prefix_declined
        n = len(self.pools)
        self.released = [0] * n  # pages released behind the window
        self.largest_table = [0] * n  # most pages a sequence held at once
        self.prefix_taken = 0  # admissions that took a prefix match
        self.prefix_declines = 0  # admissions that looked none up

    @classmethod
    def single(cls, pool: BlockPool) -> "KVPools":
        """One full kind over `pool` (its layers and widths are the
        runner's to know, not the bookkeeping's)."""
        return cls((KVKind("full", 0, 0, 0, 0),), (pool,))

    @property
    def windowed(self) -> bool:
        return any(k.window is not None for k in self.kinds)

    def stats(self) -> dict:
        out = {}
        for i, (kind, pool) in enumerate(zip(self.kinds, self.pools)):
            out[kind.name] = {
                "pages_used": pool.num_used(),
                "pages_free": pool.num_free(),
                "pages_total": pool.usable_blocks,
                "window": kind.window,
                "select": kind.select,
                "latent": kind.latent,
                "released_behind_window": self.released[i],
                "largest_table": self.largest_table[i],
                "prefix_taken": self.prefix_taken if i == 0 else 0,
                "prefix_declined": self.prefix_declines if i == 0 else 0,
            }
        return out


def window_pool_blocks(layout: KVLayout, rows: int, lanes: int) -> int:
    """Pages of a window kind's pool, the null page included: every lane
    at its bound for programs of `rows` rows (`KVLayout.lane_pages`), a
    page each for the decode step planned ahead, and two spare a lane."""
    return lanes * (layout.lane_pages(rows) + 1) + 2 * lanes


def blocks_by_kind(kinds: Sequence[KVKind], num_blocks, block_size: int,
                   rows: int, lanes: int) -> tuple[int, ...]:
    """The pools' sizes, one a kind: `num_blocks` as it is where it
    gives one a kind, else the first kind's, every window kind behind it
    sized off the lanes (`window_pool_blocks`) and any other kind as the
    first."""
    if not isinstance(num_blocks, int):
        if len(num_blocks) != len(kinds):
            raise ValueError(f"{len(num_blocks)} pool sizes for "
                             f"{len(kinds)} kinds of KV layer")
        return tuple(num_blocks)
    return (num_blocks,) + tuple(
        num_blocks if kind.window is None else window_pool_blocks(
            KVLayout.of(kind, 0, block_size), rows, lanes)
        for kind in kinds[1:])


def auto_num_blocks(
    *,
    kinds: Sequence[KVKind],
    block_size: int,
    dtype_bytes: int,
    max_model_len: int,
    max_batch_size: int,
    chunk_rows: int = 0,
    memory_fraction: float = 0.3,
    tensor_ways: int = 1,
    state_bytes: int = 0,
    device=None,
) -> int:
    """Size the first kind's pool off device memory (reference: vLLM's
    gpu memory profiling, here a static estimate: params are already
    resident, so take `memory_fraction` of the device's bytes_limit for
    KV). What is fixed in size comes out of the same budget first: the
    recurrent state of a family that has it (`state_bytes`, what
    `StateLayout.nbytes` says the lanes' state takes) and the pools of
    its window kinds (`window_pool_blocks` at programs of `chunk_rows`
    rows; 0: whole prompts).

    The CPU backend reports no memory and gets "every lane can reach
    max_model_len, twice over" (tests). A TPU that reports none is an
    error: the toy floor there would serve real traffic from a pool
    sized for a test.
    """
    # the layout's own sharding rule: sizing must not assume a split
    # the runner won't make
    layouts = [KVLayout.of(k, 0, block_size) for k in kinds]
    per_block = layouts[0].block_bytes(dtype_bytes, tensor_ways)
    fixed = state_bytes + sum(
        window_pool_blocks(lay, chunk_rows or max_model_len, max_batch_size)
        * lay.block_bytes(dtype_bytes, tensor_ways)
        for lay in layouts[1:] if lay.window is not None)
    if device is None:
        import jax

        device = jax.local_devices()[0]
    floor = max_batch_size * ((max_model_len + block_size - 1) // block_size)
    if device.platform == "cpu":
        return 2 * floor + 1  # +1: the null page
    stats = device.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']; cannot "
            f"size the KV pool — pass num_blocks explicitly")
    budget = int(stats["bytes_limit"] * memory_fraction) - fixed
    return max(floor + 1, budget // per_block)
