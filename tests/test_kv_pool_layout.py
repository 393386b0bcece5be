"""The KV pool's layout (`serve/llm/cache.py` KVLayout): what it reads and
writes against a numpy pool, its sharding over `tensor`, and — compiled for
the v5e without a chip — that no serve program copies the pool whole.

The last is the guard of PERF.md (PR 26): the padded (HK, D) minor
dimensions, a context gathered for every layer at once, and a scatter with
a leading `:` window each made XLA copy both pools through a temporary in
every program; any one of them coming back shows here as a pool-sized
`copy` and as gigabytes of temporaries."""

import collections
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import context_attention
from ray_tpu.serve.llm.cache import (
    KVKind,
    KVLayout,
    auto_num_blocks,
    blocks_by_kind,
)

# (kv_layers, num_blocks, block_size, n_kv_head, head_dim[, v_head_dim]);
# "narrow_v": a V row narrower than its K row, as mimo_v2's kinds have
LAYOUTS = {"mha": (3, 12, 4, 4, 16), "gqa": (2, 10, 16, 8, 128),
           "narrow_v": (2, 12, 8, 2, 24, 16)}


def _numpy_pool(layout, rng, width=None):
    """The pool as plain numpy holds it: (L, pages, Bs, HK, D)."""
    return rng.normal(size=(layout.kv_layers, layout.num_blocks,
                            layout.block_size, layout.n_kv_head,
                            width or layout.head_dim)).astype(np.float32)


def _as_device(layout, pool):
    return jnp.asarray(pool.reshape(pool.shape[:3] + (-1,)))


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_decode_rows_written_then_read_match_numpy(name):
    """One new row per lane, padded lanes writing to the null page 0 and
    reading it back through a table of zeros."""
    layout = KVLayout(*LAYOUTS[name])
    L, P, Bs, HK, D = LAYOUTS[name][:5]
    rng = np.random.RandomState(0)
    ref = _numpy_pool(layout, rng)
    pages = _as_device(layout, ref)
    # lanes 0, 1 real (positions 5 and Bs, the second opening a page),
    # lanes 2, 3 padding: position 0 of a table of zeros
    tables = np.zeros((4, 3), np.int32)
    tables[0], tables[1] = [7, 2, 9], [4, 8, 0]
    positions = np.asarray([5, Bs, 0, 0])
    block_ids = tables[np.arange(4), positions // Bs]
    offsets = positions % Bs
    rows = rng.normal(size=(L, 4, HK, D)).astype(np.float32)
    pages = jax.jit(layout.write)(pages, block_ids, offsets, rows)
    for lane in (0, 1):
        ref[:, block_ids[lane], offsets[lane]] = rows[:, lane]
    got = np.asarray(pages).reshape(ref.shape)
    np.testing.assert_array_equal(got[:, 1:], ref[:, 1:])
    # the null page took one of the padded lanes' rows, nothing else
    assert any(np.array_equal(got[:, 0, 0], rows[:, lane])
               for lane in (2, 3))
    np.testing.assert_array_equal(got[:, 0, 1:], ref[:, 0, 1:])
    for layer in range(L):
        ctx = jax.jit(layout.read)(pages, jnp.int32(layer), tables)
        assert ctx.shape == (4, 3 * Bs, HK, D)
        np.testing.assert_array_equal(
            np.asarray(ctx), got[layer][tables].reshape(4, 3 * Bs, HK, D))
        # slot c of a lane's context is position c of its sequence
        np.testing.assert_array_equal(np.asarray(ctx)[1, Bs], rows[layer, 1])


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_chunk_rows_crossing_a_page_match_numpy(name):
    """A chunk that starts mid-table and crosses page boundaries, its
    padded tail pointed at the null page."""
    layout = KVLayout(*LAYOUTS[name])
    L, P, Bs, HK, D = LAYOUTS[name][:5]
    rng = np.random.RandomState(1)
    ref = _numpy_pool(layout, rng)
    table = np.asarray([3, 6, 1, 5], np.int32)
    start, n, Tb = Bs, 2 * Bs + 1, 3 * Bs  # pages 6, 1 and a row of 5
    pos = start + np.arange(Tb)
    block_ids = np.where(np.arange(Tb) < n, table[pos // Bs], 0)
    offsets = pos % Bs
    rows = rng.normal(size=(L, Tb, HK, D)).astype(np.float32)
    pages = jax.jit(layout.write)(_as_device(layout, ref), block_ids,
                                  offsets, rows)
    for t in range(n):
        ref[:, block_ids[t], offsets[t]] = rows[:, t]
    got = np.asarray(pages).reshape(ref.shape)
    np.testing.assert_array_equal(got[:, 1:], ref[:, 1:])
    for layer in range(L):
        ctx = np.asarray(layout.read(pages, layer, table[None]))
        assert ctx.shape == (1, 4 * Bs, HK, D)
        np.testing.assert_array_equal(ctx[0, start:start + n],
                                      rows[layer, :n])
        np.testing.assert_array_equal(ctx[0, :start], ref[layer, 3])


# ------------------------------------------------- whole pages at a time

# the K pool and the V pool of each layout (the V rows of "narrow_v" are
# narrower): `write_pages` takes each pool's own row width, as `write`
POOLS = [(name, pool) for name in sorted(LAYOUTS) for pool in ("k", "v")]


def _page_case(name, pool, seed):
    layout = KVLayout(*LAYOUTS[name])
    width = layout.head_dim if pool == "k" else (
        layout.v_head_dim or layout.head_dim)
    rng = np.random.RandomState(seed)
    return layout, width, rng, _numpy_pool(layout, rng, width)


def _rows(layout, width, rng, n):
    return rng.normal(size=(layout.kv_layers, n, layout.n_kv_head,
                            width)).astype(np.float32)


def _page_ids(layout, table, start, n, Tb):
    """As `ModelRunner._page_ids` builds them: the table's page for a
    group with a valid row, the null page for a group of padding."""
    ids = np.zeros((layout.group_pages(Tb),), np.int32)
    valid = layout.group_pages(n)
    first = start // layout.block_size
    ids[:valid] = table[first:first + valid]
    return ids


def _rowwise(layout, pages, table, start, n, rows):
    """The write of before PR 37: a padded row to the null page."""
    pos = start + np.arange(rows.shape[1])
    block_ids = np.where(np.arange(rows.shape[1]) < n,
                         table[np.minimum(pos // layout.block_size,
                                          len(table) - 1)], 0)
    return layout.write(pages, block_ids, pos % layout.block_size, rows)


@pytest.mark.parametrize("name,pool", POOLS)
def test_page_rows_crossing_pages_match_numpy(name, pool):
    """A chunk of whole pages that starts mid-table: every row lands at
    (table[t // Bs], t % Bs), nothing else moves, not even page 0."""
    layout, width, rng, ref = _page_case(name, pool, 3)
    Bs = layout.block_size
    table = np.asarray([3, 6, 1, 5], np.int32)
    start, Tb = Bs, 3 * Bs  # pages 6, 1, 5
    rows = _rows(layout, width, rng, Tb)
    ids = _page_ids(layout, table, start, Tb, Tb)
    np.testing.assert_array_equal(ids, [6, 1, 5])
    pages = jax.jit(layout.write_pages)(_as_device(layout, ref), ids, rows)
    for t in range(Tb):
        ref[:, table[(start + t) // Bs], (start + t) % Bs] = rows[:, t]
    np.testing.assert_array_equal(np.asarray(pages).reshape(ref.shape), ref)
    if pool == "k":
        ctx = np.asarray(layout.read(pages, 1, table[None]))
        np.testing.assert_array_equal(ctx[0, start:start + Tb], rows[1])


@pytest.mark.parametrize("name,pool", POOLS)
def test_half_valid_last_page_keeps_padding_behind_the_frontier(name, pool):
    """Two full pages, a half-valid one and a group of padding: the valid
    slots are the row-wise write's, the slots behind the frontier of the
    half-valid page hold that group's padded rows, the all-padding group
    went to page 0, and every other page is untouched."""
    layout, width, rng, ref = _page_case(name, pool, 4)
    Bs = layout.block_size
    table = np.asarray([7, 2, 9, 4, 8], np.int32)
    start, n, Tb = Bs, 2 * Bs + Bs // 2, 4 * Bs  # pages 2, 9, half of 4
    rows = _rows(layout, width, rng, Tb)
    ids = _page_ids(layout, table, start, n, Tb)
    np.testing.assert_array_equal(ids, [2, 9, 4, 0])
    dev = _as_device(layout, ref)
    got = np.asarray(jax.jit(layout.write_pages)(dev, ids, rows)
                     ).reshape(ref.shape)
    old = np.asarray(_rowwise(layout, dev, table, start, n, rows)
                     ).reshape(ref.shape)
    # below the frontier: what the row-wise write leaves
    for page in (2, 9):
        np.testing.assert_array_equal(got[:, page], old[:, page])
    np.testing.assert_array_equal(got[:, 4, :Bs // 2], old[:, 4, :Bs // 2])
    # behind it, in the sequence's own page: that group's padded rows
    np.testing.assert_array_equal(
        got[:, 4, Bs // 2:],
        rows[:, 2 * Bs + Bs // 2:3 * Bs].reshape(got[:, 4, Bs // 2:].shape))
    # the null page took the group of padding, whole
    np.testing.assert_array_equal(
        got[:, 0], rows[:, 3 * Bs:].reshape(got[:, 0].shape))
    untouched = [p for p in range(layout.num_blocks) if p not in (0, 2, 9, 4)]
    np.testing.assert_array_equal(got[:, untouched], ref[:, untouched])


@pytest.mark.parametrize("name,pool", POOLS)
def test_groups_of_padding_land_on_the_null_page_only(name, pool):
    """One valid row in a bucket of four pages: its page and page 0 move,
    page 0 holding one of the three groups of padding, whole."""
    layout, width, rng, ref = _page_case(name, pool, 5)
    Bs = layout.block_size
    table = np.asarray([5, 3], np.int32)
    rows = _rows(layout, width, rng, 4 * Bs)
    ids = _page_ids(layout, table, 0, 1, 4 * Bs)
    np.testing.assert_array_equal(ids, [5, 0, 0, 0])
    got = np.asarray(jax.jit(layout.write_pages)(
        _as_device(layout, ref), ids, rows)).reshape(ref.shape)
    np.testing.assert_array_equal(got[:, 5, 0], rows[:, 0])
    assert any(np.array_equal(
        got[:, 0], rows[:, g * Bs:(g + 1) * Bs].reshape(got[:, 0].shape))
        for g in (1, 2, 3))
    untouched = [p for p in range(layout.num_blocks) if p not in (0, 5)]
    np.testing.assert_array_equal(got[:, untouched], ref[:, untouched])


@pytest.mark.parametrize("name,pool", POOLS)
def test_write_pages_and_write_agree_below_the_frontier(name, pool):
    """Random (start, n, Tb), whole pages and not: every slot below the
    frontier, and every page the program's rows do not fall in (but page
    0), is what the row-wise write leaves."""
    layout, width, rng, ref = _page_case(name, pool, 6)
    Bs = layout.block_size
    write_pages = jax.jit(layout.write_pages)
    for case in range(12):
        table = rng.permutation(np.arange(1, layout.num_blocks))[:8] \
            .astype(np.int32)
        groups = int(rng.choice([1, 2, 4]))
        Tb = groups * Bs if case % 4 else max(1, Bs // 2)
        start = Bs * int(rng.randint(0, 8 - layout.group_pages(Tb) + 1))
        n = int(rng.randint(1, Tb + 1))
        rows = _rows(layout, width, rng, Tb)
        dev = _as_device(layout, ref)
        got = np.asarray(write_pages(
            dev, _page_ids(layout, table, start, n, Tb), rows)
        ).reshape(ref.shape)
        old = np.asarray(_rowwise(layout, dev, table, start, n, rows)
                         ).reshape(ref.shape)
        ctx = lambda a: a[:, table].reshape(  # noqa: E731
            (layout.kv_layers, 8 * Bs) + a.shape[3:])
        np.testing.assert_array_equal(ctx(got)[:, :start + n],
                                      ctx(old)[:, :start + n])
        own = set(table[start // Bs:start // Bs + layout.group_pages(n)])
        others = [p for p in range(1, layout.num_blocks) if p not in own]
        np.testing.assert_array_equal(got[:, others], ref[:, others])


def _scatter_indices(fn, *args):
    """Shapes of the index operand of every scatter `fn` traces to."""
    return [eqn.invars[1].aval.shape
            for eqn in jax.make_jaxpr(fn)(*args).eqns
            if eqn.primitive.name.startswith("scatter")]


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_which_path_is_a_matter_of_shape(name):
    """Whole pages: one index a layer and PAGE, the window a whole page.
    Rows that are not whole pages (a bucket under `block_size`): one index
    a layer and ROW, to the slots the page-wise write would give them."""
    layout, width, rng, ref = _page_case(name, "k", 7)
    L, Bs = layout.kv_layers, layout.block_size
    dev = _as_device(layout, ref)
    whole, part = _rows(layout, width, rng, 2 * Bs), \
        _rows(layout, width, rng, Bs + Bs // 2)
    ids = np.asarray([4, 7], np.int32)
    assert layout.whole_pages(2 * Bs) and not layout.whole_pages(Bs // 2)
    assert layout.group_pages(Bs + Bs // 2) == 2
    assert _scatter_indices(layout.write_pages, dev, ids, whole) \
        == [(L, 2, 2)]
    assert _scatter_indices(layout.write_pages, dev, ids, part) \
        == [(L, Bs + Bs // 2, 3)]
    assert _scatter_indices(layout.write, dev, np.zeros(2 * Bs, np.int32),
                            np.zeros(2 * Bs, np.int32), whole) \
        == [(L, 2 * Bs, 3)]
    got = np.asarray(layout.write_pages(dev, ids, part)).reshape(ref.shape)
    ref[:, 4] = part[:, :Bs]
    ref[:, 7, :Bs // 2] = part[:, Bs:]
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("n_kv_head,sharded", [(4, True), (3, False)])
def test_pool_shards_whole_heads_over_tensor(cpu_mesh8, n_kv_head, sharded):
    """tensor=2: contiguous head blocks when the heads divide, replicated
    when they do not; writes and reads agree with the unsharded pool, and
    the pool's size on a device follows the same rule."""
    layout = KVLayout(2, 6, 4, n_kv_head, 16)
    rng = np.random.RandomState(2)
    pages, _ = layout.zeros(jnp.float32, cpu_mesh8)
    want = layout.shape[:3] + (layout.row // 2 if sharded else layout.row,)
    assert pages.sharding.shard_shape(pages.shape) == want
    assert layout.shard_ways(2) == (2 if sharded else 1)
    tables = np.asarray([[2, 5], [3, 0]], np.int32)
    block_ids, offsets = np.asarray([5, 3, 0]), np.asarray([1, 0, 2])
    rows = rng.normal(size=(2, 3, n_kv_head, 16)).astype(np.float32)
    with jax.set_mesh(cpu_mesh8):
        pages = jax.jit(layout.write)(pages, block_ids, offsets, rows)
        ctx = jax.jit(layout.read)(pages, jnp.int32(1), tables)
    assert pages.sharding.shard_shape(pages.shape) == want
    plain = layout.write(layout.zeros(jnp.float32)[0], block_ids, offsets,
                         rows)
    np.testing.assert_array_equal(np.asarray(pages), np.asarray(plain))
    np.testing.assert_array_equal(
        np.asarray(ctx), np.asarray(layout.read(plain, 1, tables)))
    np.testing.assert_array_equal(np.asarray(ctx)[0, 4 + 1], rows[1, 0])

    class Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 1 << 30}

    sized = {ways: auto_num_blocks(
        kinds=(KVKind("full", 2, n_kv_head, 16, 16),), block_size=4,
        dtype_bytes=2, max_model_len=64, max_batch_size=2,
        memory_fraction=0.5, tensor_ways=ways, device=Dev())
        for ways in (1, 2)}
    assert sized[2] == (2 * sized[1] if sharded else sized[1])


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_runner_decodes_the_same_on_a_tensor_mesh(cpu_mesh8, model):
    """A tensor-parallel replica's pool is sharded by the layout's spec;
    prefill, chunk and decode give the tokens of the one-device runner."""
    from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

    adapter = adapters()[model]
    cfg = dataclasses.replace(adapter.presets["tiny"](), dtype=jnp.float32,
                              remat=False)
    params = adapter.init_fn(jax.random.PRNGKey(0), cfg)

    def run(mesh):
        r = ModelRunner(adapter, cfg, params, block_size=4, num_blocks=16,
                        max_model_len=32, max_batch_size=2,
                        prefill_chunk_size=8, mesh=mesh)
        table = [3, 7, 2, 9]
        out = [r.prefill(list(range(1, 9)), table, 0.0)[0]]
        tok, logits = r.prefill_chunk(list(range(9, 15)), 8, table, 0.0)
        out.append(tok)
        for pos in (14, 15):
            toks, logits = r.decode([DecodeItem(out[-1], pos, table, 0.0)])
            out.append(toks[0])
        return out, logits, r

    want, want_logits, _ = run(None)
    got, got_logits, r = run(cpu_mesh8)
    assert got == want
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-4)
    ways = r.layouts[0].shard_ways(2)
    assert ways == 2
    assert r.k_pages[0].sharding.shard_shape(r.k_pages[0].shape)[-1] \
        == r.layouts[0].row // ways


# ----------------------------------------------- compiled for the v5e


@pytest.fixture(scope="module")
def v5e():
    """The four described (not attached) chips of a v5e 2x2 host. Only
    libtpu's absence skips: a topology that cannot be described where
    libtpu is must fail."""
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # an executable compiled for a described chip can be written to the
    # persistent cache but not read back here: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return jax.sharding.SingleDeviceSharding(v5e[0])


# model -> (family, preset, lanes, pages, monolithic prefill bucket,
# max_model_len): the serve cells' engines as benchmark/configs/ has them
MODELS = {"gpt2-large": ("gpt2", "large", 8, 512, 512, 1024),
          "olmoe-1b-7b": ("llama", "olmoe_1b_7b_l8", 16, 1088, 256, 1024),
          "nemotron-3-nano-30b-a3b": (
              "nemotron_h", "nano_30b_a3b_l18_ep4", 32, 5184, 256, 2560),
          # two kinds of KV layer: 12,288 pages of the full kind, the
          # window kind's 896 sized off the 32 lanes
          "mimo-v2.5": ("mimo_v2", "v2_5_l7_ep16", 32, 12288, 256, 8704),
          # a latent kind: a pool of latent rows (576 lanes) and a pool of
          # indexer keys (128 lanes) under one table, 24,576 pages
          "glm-5": ("glm_dsa", "glm_5_l5_ep32", 32, 24576, 256, 16768),
          # 64 lanes: 6 layers with K and V beside 18 with two rows of
          # conv state a lane, 20,480 pages
          "lfm2-8b-a1b": ("lfm2", "lfm2_8b_a1b_ep4", 64, 20480, 256, 8576),
          # 64 lanes with a float32 part: 1 layer with K and V beside 9
          # with 2.4 GB of Mamba-2 state, 7,232 pages
          "granite-4.0-h-small": (
              "granite_hybrid", "h_small_l10_ep4", 64, 7232, 256, 1792),
          # a latent kind with no indexer: ONE pool of latent rows (640
          # lanes; the second has no lanes), 49,152 pages, and a residual
          # state of 4 streams of 3584 a row
          "xing4.0-29b-a4b": (
              "xing4", "xing4_29b_a4b_l6_ep4", 32, 49152, 256, 33280)}
# A program's temporaries, bytes. With no weight cast in any program they
# are activations: the AOT compile reads 1.1-105.8 MB for gpt2-large (the
# most in prefill-512; 1.55-1.64 GB while the float32 stacks were cast
# inside), 4.4-136.4 MB for OLMoE (decode-16), and 62.8 MB (decode-32),
# 75.9 MB (chunk-256) and 91.2 MB (prefill-256) for the nemotron_h cut
# (527.3 MB in chunk-256 while the conv window was one (3, 6144) part a
# slot, which XLA relaid out around every program); the mimo_v2 cut's are
# printed by the test (PERF.md section 6, PR 34)
TEMP_BOUND = 0.3e9
# the glm_dsa cut's chunk folds latent tiles of 1,024 slots under the
# indexer's choice: the scores of 64 heads x 256 rows on a tile are 67 MB in
# float32, and the program holds a few at once (413 MB in chunk-256, 90 MB
# in decode-32, 103 MB in prefill-256: the AOT compile, PR 40)
# (the xing4 cut's chunk folds latent tiles of 1,024 slots for 32 heads x
# 256 rows, 34 MB of float32 scores a tile: 142 MB in chunk-256, 75 MB in
# decode-32 on the loops and 31 MB with the kernel, 55 MB in prefill-256,
# under the common bound: PRs 51 and 52)
TEMP_BOUNDS = {"glm-5": 0.5e9}


@pytest.fixture(scope="module", params=sorted(MODELS))
def served_runner(one_chip, request):
    """A served model's runner as its cells configure it (pages of 16,
    chunks of 256, verify width 5) over shapes alone: no weights, and a
    two-page pool in place of the real one — the programs take their pool
    as an argument, and get the real shape. The parameter shapes are the
    resident tree's: what the adapter makes of a fresh init, which is what
    a runner holds and every program is called with. Also returns the
    shapes of the leaves the adapter cast, for the `convert` assertion."""
    from ray_tpu.serve.llm.runner import ModelRunner, adapters

    family, preset, lanes, pages, _, max_len = MODELS[request.param]
    adapter = adapters()[family]
    cfg = adapter.presets[preset]()

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def init(k):
        return adapter.init_fn(k, cfg)

    given = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = jax.tree.map(shape, jax.eval_shape(
        lambda k: adapter.resident_fn(init(k), cfg), jax.random.PRNGKey(0)))
    cast = [r for g, r in zip(jax.tree.leaves(given),
                              jax.tree.leaves(params)) if g.dtype != r.dtype]
    kinds = adapter.kv_kinds(cfg)
    runner = ModelRunner(adapter, cfg, params, block_size=16,
                         num_blocks=[2] * len(kinds),
                         max_model_len=max_len, max_batch_size=lanes,
                         prefill_chunk_size=256, num_draft_tokens=4)
    # (K pools, V pools) at their real sizes, one of each a kind of KV
    # layer; bare where the family has one kind
    real = [dataclasses.replace(lay, num_blocks=n) for lay, n in zip(
        runner.layouts, blocks_by_kind(kinds, pages, 16, 256, lanes))]
    pool = tuple(
        tuple(jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one_chip)
              for shape in shapes)
        for shapes in ([lay.shape for lay in real],
                       [lay.v_shape for lay in real]))
    if len(kinds) == 1:
        pool = (pool[0][0], pool[1][0])
    assert runner.weights["cast_leaves"] == 0  # resident shapes given
    # the lanes' recurrent state at its real size ({}: the family has none)
    state = {}
    if runner.state_layout is not None:
        state = {part[0]: jax.ShapeDtypeStruct(
            runner.state_layout.shape(part), part[2], sharding=one_chip)
            for part in runner.state_layout.parts}
    return request.param, runner, params, (pool, state), cast


# program -> (runner method, the kind and the bucket of the pack it takes
# from the host (`runner.pack_layout`: tokens, positions, page or block ids,
# tables, the sampling fields and the step as ONE int32 array since PR 56,
# eight to ten arguments until then), whether it takes, before the pack,
# the device-resident last sampled ids ("s" of them, PR 31) and the lanes'
# recurrent state ({} but for a family that has it)). "p" is the
# monolithic prefill bucket, "s" the decode lanes. A prompt's and a
# chunk's programs read one page id a group of 16 rows since PR 37
PROGRAMS = {
    "prefill": ("_prefill_impl", "prefill", "p", True),
    "chunk-256": ("_chunk_impl", "chunk", 256, True),
    "verify-5": ("_verify_impl", "verify", 5, False),
    "decode": ("_decode_impl", "decode", "s", True),
}


# the running softmax that a tile loop of `attend_cached` carries (as
# benchmark/attn_ops.py finds it): (t, m, l, acc, ...) for G lanes of T rows
_SOFTMAX_CARRY = re.compile(
    r"\(s32\[\], f32\[(\d+),(\d+),(\d+),(\d+)\], f32\[\1,\2,\3,\4\], "
    r"f32\[\1,\4,\2,\3,(\d+)\]")


@pytest.mark.parametrize("program,paged", [
    ("prefill", False), ("chunk-256", False), ("verify-5", False),
    ("decode", False), ("decode", True), ("verify-5", True)])
def test_no_serve_program_copies_the_pool(one_chip, served_runner, program,
                                          paged, monkeypatch):
    """No `copy` of the pool's size, or of a part of the recurrent state's
    (aliased through the `ssm_step` kernels of a decode program or not),
    in any program, and no `convert` of a weight: gpt2-large's resident
    tree holds what the forwards cast (embeddings, kernels, biases) in bf16
    and the layer norms in float32, OLMoE's (16 KV heads of 128, 8 layers,
    1,088 pages) and the nemotron_h cut's (2 layers with K and V, 8 with
    0.55 GB of state for 32 lanes) are created in the compute dtype. With
    the cast gone gpt2-large's temporaries are activations only.

    `paged`: decode and verify as the chip compiles them, a full kind
    whose K and V are alike read by the Pallas kernel (gpt2-large, OLMoE,
    the nemotron_h cut: one `tpu_custom_call` in the layer scan, no loop
    that carries a running softmax), and since PR 52 the xing4 cut's
    latent kind, which is read whole and has one pool (one call a layer
    of the six it unrolls, its result every head's 512 values a lane, the
    one pool its only pool-sized operand); not `paged`: the same programs
    on the tile loops, which the mimo_v2 cut's full kind (K 192, V 128),
    the glm_dsa cut's latent kind (read under its indexer's choice) and
    every chunk keep. The pool is copied on neither path."""
    # kernels are chosen by `jax.default_backend()`: take the chip's side
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, runner, params, (pool, state), cast = served_runner
    by_kernel = [context_attention.reads_by_kernel(
        lay, 5 if program == "verify-5" else 1) for lay in runner.layouts]
    if paged and not any(by_kernel):
        pytest.skip("no kind of this model's KV layers is the kernel's")
    if not paged and any(by_kernel):  # else: as the chip compiles it
        monkeypatch.setattr(context_attention, "reads_by_kernel",
                            lambda *a, **k: False)
    if program == "verify-5" and state:
        pytest.skip("the engine refuses speculation for a stateful family")
    if program == "verify-5" and len(runner.layouts) > 1:
        pytest.skip("the engine refuses speculation with a window kind")
    if program == "verify-5" and runner.layouts[0].latent:
        pytest.skip("the engine refuses speculation with a latent kind")
    method, kind, bucket, carries_ids = PROGRAMS[program]
    sizes = {"p": MODELS[model][4], "s": runner.max_batch_size}

    def compiled_for(sizes):
        def i32(n):
            return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

        host = i32(runner._layout(kind, sizes.get(bucket, bucket))[0])
        args = [i32(sizes["s"]), state, host] if carries_ids else [host]
        donate = (1, 2, 4) if carries_ids else (1, 2)
        return jax.jit(getattr(runner, method), donate_argnums=donate) \
            .lower(params, *pool, *args).compile()

    def reads_of(text):
        """(the read's kernels, the running-softmax carries of its loops)
        in a compiled program."""
        return ([line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and "ctx_read_paged" in line],
                [tuple(map(int, m.groups()))
                 for m in _SOFTMAX_CARRY.finditer(text)])

    compiled = compiled_for(sizes)
    text = compiled.as_text()
    print(f"{model} {program}: temporaries "
          f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:.1f} MB")
    kernels, carries = reads_of(text)
    if paged:
        # the read is one kernel in the scanned layer, nothing of it loops
        # (the nemotron_h cut unrolls its two layers with K and V)
        assert len(kernels) in (1, runner.layouts[0].kv_layers), len(kernels)
        assert not carries, carries
        if runner.layouts[0].latent:
            c = runner.cfg
            assert len(kernels) == runner.layouts[0].kv_layers
            pool_shape = "bf16[" + ",".join(
                map(str, jax.tree.leaves(pool)[0].shape)) + "]"
            for line in kernels:
                assert f"= bf16[{sizes['s']},{c.num_attention_heads}," \
                    f"{c.kv_lora_rank}]" in line, line[:200]
                assert line.split("operand_layout_constraints")[1].split(
                    "}}")[0].count(pool_shape) == 1, line[:900]
        # gpt2-large's smaller decode buckets alike
        for n in (1, 2, 4) if (model, program) == ("gpt2-large",
                                                   "decode") else ():
            kernels, carries = reads_of(
                compiled_for({**sizes, "s": n}).as_text())
            assert len(kernels) == 1 and not carries, (n, carries)
    elif program != "prefill":
        # a chunk's 256 rows, the mimo_v2 cut's full kind (K 192, V 128)
        # and the glm_dsa cut's latent kind keep their loops on the chip
        assert carries and not kernels

    def results(opcode):
        return [tuple(map(int, m.group(1).split(","))) for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* " + opcode + r"\(", text)]

    # the smallest pool, which the SSM part of the state is larger than
    # (a latent kind with no indexer has a second pool of no lanes)
    pools = [a for a in jax.tree.leaves(pool) if math.prod(a.shape)]
    pool_elements = min(math.prod(a.shape) for a in pools)
    assert all(math.prod(a.shape) >= pool_elements
               for name, a in state.items() if name == "ssm")
    assert not [r for r in results("copy")
                if math.prod(r) >= pool_elements]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < TEMP_BOUNDS.get(model, TEMP_BOUND)
    # what the adapter cast, or the family creates in the compute dtype
    # (but nemotron_h's `conv_w`, a (4, 6144) filter applied in float32
    # beside the state)
    weights = cast or [
        a for path, a in jax.tree_util.tree_leaves_with_path(params)
        if a.ndim >= 2 and "conv_w" not in jax.tree_util.keystr(path)
        # the xing4 streams' maps are float32 and applied in float32
        and "hc_" not in jax.tree_util.keystr(path)]
    assert weights and all(a.dtype == runner.cfg.dtype for a in weights)
    # a weight, a layer of a stack, or an expert of a layer
    held = {a.shape[i:] for a in weights for i in range(a.ndim - 1)}
    assert not [r for r in results("convert") if r in held]
    if program == "decode" and not paged:
        assert not _full_width_contexts(text, runner, [
            *jax.tree.leaves(pool), *params_and_state(params, state)])
    if runner.adapter.name == "xing4":
        # the residual state (rows, 4 x 3584) is read by a half-layer's
        # maps and mixes where it lies: the one copy of a program's rows
        # of it is the entry's (the token's row laid out four times),
        # and none of its twelve half-layers adds another; the maps and
        # every Sinkhorn iteration are ONE kernel a half-layer, not some
        # eighty fusions of a 4 x 4 a row
        rows = {"prefill": sizes["p"], "chunk-256": 256}.get(
            program, runner.max_batch_size)
        state_elements = rows * runner.cfg.hc_mult * runner.cfg.hidden_size
        assert len([r for r in results("copy") + results("transpose")
                    if math.prod(r) >= state_elements
                    and r[-1] % runner.cfg.hidden_size == 0]) <= 1
        maps = [line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line
                and "mhc_maps" in line]
        assert len(maps) == 2 * runner.cfg.n_layer, len(maps)
        n = runner.cfg.hc_mult
        assert not [r for r in results("fusion") if r[:2] == (n, n)]
    if program == "decode" and "ssm" in state:
        # the one-step recurrence passes over a layer's state once: one
        # `ssm_step` kernel a Mamba layer, its FIRST result the state
        # buffer it is given (the readers of benchmark/ssm_ops.py and
        # ssm_g1_ops.py tell a state update by that), and nothing else of
        # the program reads the buffer (a read-out beside an in-place
        # update was a fusion of its own over a whole layer of it: PR 49)
        steps, others = _state_steps(text, state["ssm"])
        assert len(steps) == runner.state_layout.layers, steps
        assert not others, others
    if program in ("prefill", "chunk-256"):
        # a prompt's and a chunk's rows are stored a page at a time: each
        # pool's scatter has one update a layer and PAGE of the program's
        # rows (36 x 16 at gpt2-large's chunk of 256, where it had 36 x
        # 256), its window one whole (16, row) page
        rows = sizes["p"] if program == "prefill" else 256
        updates = _pool_scatters(text, pools)
        print(f"{model} {program}: pool scatters {updates}")
        assert len(updates) == len(pools)
        for shape, n, window in updates:
            assert n == shape[0] * rows // 16
            assert window == (16, shape[-1])


def _state_steps(text, ssm):
    """(the result types of the `ssm_step` kernels that take the SSM
    state buffer `ssm` and give it back first, every other operation of
    the entry computation that takes the buffer) in a compiled program;
    the buffer handed on (a tuple's element, the program's result) does
    not read it."""
    shape = "f32[" + ",".join(map(str, ssm.shape)) + "]"
    entry = text[text.index("\nENTRY "):]
    held, steps, others = set(), [], []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*)$",
            entry, re.M):
        name, result, opcode, rest = m.groups()
        reads = held & set(re.findall(r"%([\w.-]+)", rest.split("), ")[0]))
        if shape in result:
            held.add(name)
        if opcode == "custom-call" and "ssm_step" in name and reads:
            assert result.startswith("(" + shape), result
            steps.append(result)
        elif reads and opcode not in ("get-tuple-element", "tuple"):
            others.append((name, opcode))
    return steps, others


def _pool_scatters(text, pools):
    """(pool shape, updates, window) of every scatter of a compiled
    program whose result has as many elements as one of `pools` and ends
    in its row: the updates are the elements of its `updates` operand over
    those of its window (`update_window_dims`)."""
    shape_of = {m.group(1): tuple(map(int, m.group(2).split(",")))
                for m in re.finditer(r"(%[\w.\-]+) = \w+\[([\d,]+)\]", text)}
    found = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* scatter\(([^)]*)\), "
            r"update_window_dims=\{([\d,]*)\}", text):
        result = tuple(map(int, m.group(1).split(",")))
        pool = next((a.shape for a in pools
                     if math.prod(a.shape) == math.prod(result)
                     and a.shape[-1] == result[-1]), None)
        if pool is None:
            continue
        updates = shape_of[m.group(2).split(",")[-1].strip()]
        window = tuple(updates[int(d)] for d in m.group(3).split(","))
        found.append((pool, math.prod(updates) // math.prod(window), window))
    return found


def params_and_state(params, state):
    return jax.tree.leaves(params) + list(state.values())


# the context of every lane gathered to `max_model_len`, as the decode
# programs of before PR 33 held it (PERF_LEDGER.jsonl, PR 32, `device_ops`)
FULL_WIDTH = ("[8,1024,20,64]", "[8,64,16,1280]", "[1024,16,2048]",
              "[16,1024,16,128]", "[32,2560,2,128]", "[5120,16,256]")


def _full_width_contexts(text, runner, arguments):
    """Arrays of a compiled decode program that hold as much as the
    context of all its lanes read to `max_model_len`: the named shapes of
    before, or anything of that size that is not a program argument (the
    pool, a weight, the state) or a layer or an expert of one. A group of
    lanes reads at most its own lanes' context, an eighth of that."""
    whole = (runner.max_batch_size * runner.max_blocks_per_seq
             * runner.block_size * runner.layouts[0].row)
    known = {a.shape[i:] for a in arguments for i in range(a.ndim)}
    shapes = {tuple(map(int, m.group(1).split(",")))
              for m in re.finditer(r"\w+\[([\d,]+)\]", text)}
    def inner(s):  # a slice of a stack keeps its leading 1
        return s[next(i for i, d in enumerate(s + (0,)) if d != 1):]

    return [s for s in sorted(shapes)
            if math.prod(s) >= whole and inner(s) not in known] \
        + [s for s in FULL_WIDTH if s in text]


# ------------------------------ the flash kernel's gradient, for the v5e

# The train cells' attention, (32, 1024, 12, 64) bf16. "model": as
# models/gpt2.py calls it, on (B, T, H*D) arrays seen as heads (XLA gives
# a 4-D PARAMETER a layout that pads 12 x 64 to 16 x 128, which only a
# program whose arguments are the heads themselves ever sees); "heads":
# that program, the 4-D arguments ISSUE 35 names
FLASH_SHAPE = (32, 1024, 12, 64)
# Temporaries of forward and gradient, bytes: 906,066,432 while q, k, v,
# o and their gradients were copied to (B*H, T, D) and back and the row
# statistics ended in a dimension of 1; 0 ("model") and 151 MB ("heads":
# the padded parameters unpadded) since PR 35
FLASH_TEMP_BOUND = 450e6


@pytest.mark.parametrize("arguments", ["model", "heads"])
def test_flash_gradient_is_dense_on_the_v5e(one_chip, arguments):
    """Compiled for the v5e, `grad(flash_attention)` at the train cells'
    shape holds no operand or result of a kernel whose minor dimension is
    1 (201 MB where 1.5 are meant), no transposing copy of a
    `[.., 1024, 64]` tensor, and few temporaries; called as the model
    calls it, no copy of anything as large as q at all."""
    from ray_tpu.ops.flash_attention import flash_attention, plan

    B, T, H, D = FLASH_SHAPE
    assert plan(T, H, D)["heads_per_block"] == 2
    shape = FLASH_SHAPE if arguments == "heads" else (B, T, H * D)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def attention(q, k, v):
        q, k, v = (t.reshape(FLASH_SHAPE) for t in (q, k, v))
        return flash_attention(q, k, v).reshape(shape)

    def forward_and_gradient(q, k, v, do):
        o, vjp = jax.vjp(attention, q, k, v)
        return o, vjp(do)

    compiled = jax.jit(forward_and_gradient).lower(x, x, x, x).compile()
    text = compiled.as_text()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    print(f"flash gradient, {arguments}: temporaries {temporaries / 1e6:.1f}"
          " MB")
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4  # forward, delta, dq, dkv
    for line in calls:
        head = line.split("custom_call_target")[0]
        assert not re.search(r"\[[\d,]*,1\]", head), head
    copies = [tuple(map(int, m.group(1).split(","))) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* copy\(", text)]
    assert not [c for c in copies if c[-2:] == (T, D)]
    if arguments == "model":
        assert not [c for c in copies if math.prod(c) >= B * T * H * D]
    assert temporaries < FLASH_TEMP_BOUND


# --------------------------------- the train step's loss head, for the v5e


def _materialised(text):
    """(name, result) of every instruction of a compiled module that
    writes its result to memory: those outside the fused computations."""
    fused, out = False, []
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            fused = line.lstrip("%").startswith("fused_computation")
        m = re.match(r"\s+(?:ROOT )?(\S+) = (\(.*?\)|\S+) [\w-]+\(", line)
        if m and not fused:
            out.append(m.groups())
    return out


@pytest.fixture(scope="module", params=[1, 4])
def train_step(v5e, request):
    """(chips, B, T, cfg, the compiled text) of gpt2-small's train step as
    the train cells run it (32 x 1024 a chip, adamw, the state donated),
    compiled for one described v5e chip and for a `data=4` mesh of the
    four. About 15 s each."""
    import optax

    from ray_tpu.models.gpt2 import (
        GPT2Config,
        gpt2_loss,
        gpt2_partition_rules,
        init_gpt2,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.spmd import (
        TrainState,
        batch_shardings,
        make_train_step,
        state_shardings,
    )

    chips, B, T = request.param, 32, 1024
    cfg = GPT2Config.small()
    tx = optax.adamw(3e-4, weight_decay=0.1)
    mesh = build_mesh(MeshSpec(data=-1), devices=v5e[:chips])

    def described(shapes, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings)

    state = jax.eval_shape(
        lambda key: TrainState.create(init_gpt2(key, cfg), tx),
        jax.random.PRNGKey(0))
    batch = {name: jax.ShapeDtypeStruct((B * chips, T), jnp.int32)
             for name in ("tokens", "targets")}
    step = make_train_step(lambda p, b: gpt2_loss(p, b, cfg), tx)
    with jax.set_mesh(mesh):
        compiled = step.jitted.lower(
            described(state, state_shardings(gpt2_partition_rules(), state,
                                             mesh)),
            described(batch, batch_shardings(mesh, batch))).compile()
    return chips, B, T, cfg, compiled.as_text()


def test_train_step_writes_no_float32_logits(train_step):
    """Compiled for the v5e, gpt2-small's train step writes the logits
    out in bf16, as the head's product returns them, and nothing else of
    their size in any dtype: the loss reads them by reductions
    (`ops/cross_entropy.py`), which fuse with their producer. While the
    target's log-probability was gathered from `log_softmax`, the step
    wrote `logits - max` out as `f32[B,T,50304]` for the gather to index,
    15 ms of a 282 ms step at 32 x 1024 (PERF.md section 6, PR 45)."""
    chips, B, T, cfg, text = train_step
    results = _materialised(text)
    assert len(results) > 100
    # a chip's share of the logits, under any leading shape
    elements = B * T * cfg.padded_vocab
    wide = {}
    for name, result in results:
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", result):
            if math.prod(map(int, dims.split(","))) >= elements:
                wide.setdefault(dtype, []).append(name)
    print(f"train step on {chips} chip(s): {wide}")
    assert set(wide) == {"bf16"}, wide


Fusion = collections.namedtuple(
    "Fusion", "name result kind cycles operands product")


def _fusions(text):
    """Every fusion instruction of a compiled module: its name, result,
    kind, the compiler's estimated cycles (or None), its operands' names,
    and whether the fused computation holds a `convolution`, which is
    what a product is on the TPU."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name] = []
        elif name:
            bodies[name].append(line)
    out = []
    for lines in bodies.values():
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?(\S+) = (\(.*?\)|\S+) fusion\((.*?)\)"
                         r", kind=(\w+), calls=(\S+?),", line)
            if m:
                name, result, operands, kind, calls = m.groups()
                cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
                out.append(Fusion(
                    name, result, kind, cycles and int(cycles.group(1)),
                    re.findall(r"%[\w.-]+", operands),
                    any(" convolution(" in b for b in bodies[calls])))
    return out


def test_train_step_parts_the_head_product_from_the_update(train_step):
    """Compiled for the v5e, the head's backward product `dlogits^T @ x`
    (the gradient of `wte`, with the look-up's scatter-add result added
    in) is an operation of its own, and `wte`'s adamw update an
    elementwise one that also returns this leaf's term of the gradient
    norm, as every other leaf's is. Until `make_train_step` fenced the
    gradients from their consumers, one chip ran the update (param, mu
    and nu, float32 in and out) as an output fusion BEHIND the product,
    45.1 M estimated cycles and 31 ms a step at 32 x 1024 where the
    product alone is 20.6 M and the update 2.4 M (PERF.md section 6, PR
    60); on four chips the all-reduce already stood between them. The
    fence stands BEFORE `optax.global_norm`: after it, every leaf's
    squared-norm term becomes a reduction of its own that reads the
    gradient a second time."""
    chips, _, _, cfg, text = train_step
    fusions = _fusions(text)
    wte = f"[{cfg.padded_vocab},{cfg.n_embd}]"
    for f in fusions:
        if f.product:
            assert f.result.count(wte) <= 1, f
    # the norm's terms ride in the updates: no operation returns scalars
    # alone from an operand as large as the smallest gradient
    sizes = {name: max((math.prod(map(int, d.split(",")))
                        for d in re.findall(r"\[([\d,]+)\]", result)),
                       default=1)
             for name, result in _materialised(text)}
    alone = [f.name for f in fusions
             if not re.search(r"\[\d", f.result)
             and any(sizes.get(o, 1) >= cfg.n_embd for o in f.operands)]
    assert not alone, alone
    updates = [f for f in fusions
               if f.result.count("f32" + wte) == 3 and "f32[]" in f.result]
    assert [f.kind for f in updates] == ["kLoop"], updates
    products = [f for f in fusions if f.product and wte in f.result]
    assert [f.kind for f in products] == ["kOutput"], products
    print(f"train step on {chips} chip(s): the product "
          f"{products[0].name} {products[0].cycles:,} cycles, the update "
          f"{updates[0].name} {updates[0].cycles:,} cycles")
