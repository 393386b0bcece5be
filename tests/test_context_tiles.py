"""A lane's cached context is read to its length (ISSUE 33): the dense
serve programs read the context in tiles of whole pages and only the
tiles that a small group of lanes reaches, and compute what the read of
every slot to `max_model_len`, masked, computed.

The toy shapes here would fit one tile of the real size, so the tests
shrink the tile (`KVLayout.tile_pages`) to two pages; "full width" is the
same code with a tile that holds the whole table, which reads and masks
every slot of every lane, as the programs of before did."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.context_attention import (
    CachedContext,
    attend_cached,
    softmax_over,
)
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.cache import KVLayout

BS, HK, D = 4, 2, 8  # page rows, KV heads, head width
TILE_PAGES = 2
TILE = TILE_PAGES * BS


@pytest.fixture
def small_tiles(context_tile_pages):
    context_tile_pages(TILE_PAGES)


WHOLE_TABLE = 1 << 10  # pages: more than any table here holds


def _pools(layout, dtype, seed=0):
    k, v = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k, layout.shape, jnp.float32).astype(dtype),
            jax.random.normal(v, layout.shape, jnp.float32).astype(dtype))


def _full_width(q, k, v, own_valid, layout, pools, tables, lengths, layer,
                dtype):
    """Every slot of every lane read and masked: the read of before."""
    kc = layout.read(pools[0], layer, tables)
    vc = layout.read(pools[1], layer, tables)
    C = kc.shape[1]
    cached = jnp.broadcast_to(
        (jnp.arange(C)[None, :] < lengths[:, None])[:, None, :],
        (q.shape[0], q.shape[1], C))
    return softmax_over(q, [(kc, vc, cached), (k, v, own_valid)],
                        1.0 / D ** 0.5, dtype)


def _rows(B, T, R, dtype, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, HK, R, D)).astype(dtype),
            jax.random.normal(ks[1], (B, T, HK, D)).astype(dtype),
            jax.random.normal(ks[2], (B, T, HK, D)).astype(dtype))


def test_the_tile_is_derived_from_the_pool():
    """From the page, the row and the layers that have keys and values,
    and nothing else: 32 pages of 16 at gpt2-large (36 layers of 1280), 8
    at the OLMoE cut (8 of 2048), 16 at the nemotron_h cut (2 of 256);
    never less than a page, always a power of two."""
    def pages(layers, heads, width, block=16):
        return KVLayout(layers, 4, block, heads, width).tile_pages

    assert pages(36, 20, 64) == 32 and pages(8, 16, 128) == 8
    assert pages(2, 2, 128) == 16
    assert pages(1, 64, 256) == 1 and pages(12, 12, 64) == 32
    assert pages(16, 16, 128) == 16 and pages(3, 20, 64) == 4


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("max_blocks", [10, 9], ids=["whole-tiles", "ragged"])
@pytest.mark.parametrize("group,R", [(1, 1), (2, 1), (4, 3)])
def test_decode_lanes_of_every_length_match_the_full_width_read(
        small_tiles, dtype, atol, max_blocks, group, R):
    """Positions 0, 1, tile - 1, tile, tile + 1 and the last slot, mixed in
    one batch with padded lanes (position 0, the null table), in groups of
    1, 2 and 4 rows and with grouped query heads."""
    layout = KVLayout(2, 24, BS, HK, D)
    assert layout.tile_pages == TILE_PAGES
    pools = _pools(layout, dtype)
    C = max_blocks * BS
    lengths = jnp.asarray([C - 1, TILE + 1, TILE, TILE - 1, 1, 0, 0, 0],
                          jnp.int32)
    B = lengths.shape[0]
    rng = np.random.RandomState(0)
    tables = rng.randint(1, 24, size=(B, max_blocks)).astype(np.int32)
    tables[5:] = 0  # padded lanes
    tables = jnp.asarray(tables)
    q, k, v = _rows(B, 1, R, dtype)
    own = jnp.ones((B, 1, 1), bool)
    for layer in (0, 1):
        ctx = CachedContext.of(layout, *pools, tables, lengths, group)
        got = jax.jit(lambda q, k, v: attend_cached(
            q, k, v, own, ctx, layer, dtype))(q, k, v)
        want = _full_width(q, k, v, own, layout, pools, tables, lengths,
                           layer, dtype)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=atol)
    # ordered longest first, a group reaches what its own lanes do
    longest = np.asarray(lengths).reshape(-1, group).max(axis=1)
    assert np.asarray(ctx.reach).tolist() == (-(-longest // TILE)).tolist()
    # the order is the runner's to choose: lanes in any order give each
    # lane the same row
    order = np.asarray([5, 2, 7, 0, 4, 1, 6, 3])
    mixed = CachedContext.of(layout, *pools, tables[order], lengths[order],
                             group)
    again = attend_cached(q[order], k[order], v[order], own, mixed, 1, dtype)
    np.testing.assert_allclose(np.asarray(again, np.float32),
                               np.asarray(want, np.float32)[order], atol=atol)


@pytest.mark.parametrize("start", [0, TILE, TILE + BS, 4 * TILE],
                         ids=["start-0", "tile-edge", "mid-tile", "long"])
@pytest.mark.parametrize("T,last", [(8, 7), (8, 4), (5, 2)],
                         ids=["chunk", "padded-chunk", "verify-window"])
def test_a_chunk_or_a_window_matches_the_full_width_read(small_tiles, start,
                                                         T, last):
    """One sequence a program: the context is read to `start`, none of it
    where `start` is 0; causal within the program's own rows, of which the
    first `last + 1` are real."""
    layout = KVLayout(1, 24, BS, HK, D)
    pools = _pools(layout, jnp.float32, seed=3)
    table = jnp.asarray(np.random.RandomState(1).randint(
        1, 24, size=(1, 10)).astype(np.int32))
    q, k, v = _rows(1, T, 2, jnp.float32, seed=4)
    own = jnp.tril(jnp.ones((T, T), bool))[None] \
        & (jnp.arange(T) <= last)[None, None, :]
    lengths = jnp.asarray([start], jnp.int32)
    ctx = CachedContext.of(layout, *pools, table, lengths)
    assert int(ctx.reach[0]) == -(-start // TILE)
    got = attend_cached(q, k, v, own, ctx, 0, jnp.float32)
    want = _full_width(q, k, v, own, layout, pools, table, lengths, 0,
                       jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def _toy(model):
    from ray_tpu.models import gpt2

    kw = dict(block_size=BS, num_blocks=96, max_model_len=48,
              max_batch_size=8, prefill_chunk_size=8, seed=0)
    if model == "gpt2":
        kw.update(model="gpt2", model_config=dataclasses.replace(
            gpt2.GPT2Config.tiny(), dtype=jnp.float32, remat=False))
    elif model == "nemotron_h":
        kw.update(model="nemotron_h", preset="tiny")
    else:
        kw.update(model="llama", preset="olmoe_tiny")
    return kw


def _serve(engine, prompts, max_tokens):
    streams = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=n, logprobs=True))
        for p, n in zip(prompts, max_tokens)]
    turns = 0
    while engine.step() or any(s.final() is None for s in streams):
        turns += 1
        assert turns < 3000
    out = []
    for s in streams:
        events = list(s)
        out.append(([e["token"] for e in events],
                    [e["logprob"] for e in events]))
    return out


# the parity tolerance of the families' own tests (test_serve_llm.py's
# engine-against-model comparison, test_olmoe.py's, test_nemotron_h.py's)
LOGPROB_ATOL = 2e-4


@pytest.mark.parametrize("model", ["gpt2", "olmoe", "nemotron_h"])
@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "verify"])
def test_greedy_streams_equal_the_full_width_paths(context_tile_pages,
                                                   model, speculative):
    """An engine run on the seeded toy models: prompts shorter and longer
    than a chunk, lanes of mixed lengths in one decode program (8 lanes: a
    full bucket and a padded one), chunks at tile edges and mid-tile, and
    speculation's verify window. The tokens are the full-width path's; the
    log-probs within the families' parity tolerance (a float32 sum taken
    in tiles has another order, which could flip a tie between two equal
    logits: none does on these seeds)."""
    if speculative and model == "nemotron_h":
        pytest.skip("the engine refuses speculation for a stateful family")
    kw = _toy(model)
    if speculative:
        kw["speculative"] = {"num_draft_tokens": 3}
    rng = np.random.RandomState(5)
    lengths = [3, 9, 17, 30, 5, 12, 24, 7, 2, 20]
    # repeated tokens, so that the n-gram proposer has drafts to verify
    prompts = [np.tile(rng.randint(1, 60, size=4), 9)[:n].tolist()
               for n in lengths]
    max_tokens = [14, 9, 12, 16, 10, 8, 6, 15, 11, 7]

    def run(tile_pages):
        context_tile_pages(tile_pages)
        engine = LLMEngine(EngineConfig(**kw))
        return _serve(engine, prompts, max_tokens), engine.stats()

    (tiled, stats), (full, full_stats) = run(TILE_PAGES), run(WHOLE_TABLE)
    assert [t for t, _ in tiled] == [t for t, _ in full]
    for (_, got), (_, want) in zip(tiled, full):
        np.testing.assert_allclose(got, want, atol=LOGPROB_ATOL)
    # the tiled engine read less, and the full-width one everything
    read = stats["context"]["decode"]
    assert 0 < read["slots_valid"] <= read["slots_read"] \
        < read["slots_full"]
    whole = full_stats["context"]["decode"]
    assert whole["slots_read"] >= 0.5 * whole["slots_full"]
    if speculative:
        assert stats["spec_accepted"] > 0
        assert stats["context"]["verify"]["slots_read"] > 0


def test_the_counts_add_up_on_a_scripted_run(small_tiles):
    """`stats()["context"]` by kind of program, and the same numbers as
    `serve_llm_ctx_slots_total{kind,what}`: a 20-token prompt in chunks of
    8 (at `start` 0, 8 and 16), then decode steps at positions 20.."""
    from ray_tpu.util.metrics import prometheus_text
    from ray_tpu.util.watchtower import parse_prometheus

    def exported():
        return {dict(tags)["kind"] + "." + dict(tags)["what"]: n
                for (name, tags), n in
                parse_prometheus(prometheus_text()).items()
                if name == "serve_llm_ctx_slots_total"
                and dict(tags)["model"] == "gpt2"}

    before = exported()  # other engines of this process counted too
    engine = LLMEngine(EngineConfig(**_toy("gpt2")))
    C = 48
    prompt = list(range(1, 21))
    (tokens, _), = _serve(engine, [prompt], [5])
    got = engine.stats()["context"]
    # chunks: start 0 reads nothing, 8 one tile, 16 two
    assert got["prefill"] == {"slots_read": 0 + TILE + 2 * TILE,
                              "slots_valid": 0 + 8 + 16,
                              "slots_reach": 0 + 8 + 16,
                              "slots_full": 3 * C}
    # 4 decode programs of one lane (the first token is the last chunk's),
    # at positions 20, 21, 22, 23: three tiles each
    assert len(tokens) == 5
    assert got["decode"] == {"slots_read": 4 * 3 * TILE,
                             "slots_valid": 20 + 21 + 22 + 23,
                             "slots_reach": 20 + 21 + 22 + 23,
                             "slots_full": 4 * C}
    assert got["verify"] == dict.fromkeys(got["verify"], 0)
    after = exported()
    assert {key: n - before.get(key, 0) for key, n in after.items()
            if n != before.get(key, 0)} == {
        kind + "." + what: n for kind, counts in got.items()
        for what, n in counts.items() if n}


def test_a_group_reads_to_its_longest_lane_and_padding_costs_nothing(
        small_tiles):
    """The runner's count for one decode program of 16 rows, two a group:
    11 lanes ordered by position, 5 padded rows behind them."""
    from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

    adapter = adapters()["gpt2"]
    cfg = dataclasses.replace(adapter.presets["tiny"](), dtype=jnp.float32,
                              remat=False)
    params = adapter.init_fn(jax.random.PRNGKey(0), cfg)
    r = ModelRunner(adapter, cfg, params, block_size=BS, num_blocks=64,
                    max_model_len=48, max_batch_size=16)
    assert [r.lanes_per_group(n) for n in (1, 2, 4, 8, 16, 32)] \
        == [1, 1, 1, 2, 2, 4]
    positions = [3, 40, 9, 17, 1, 25, 8, 33, 16, 2, 24]
    table = list(range(1, 13))
    ids, _ = r.decode([DecodeItem(1, p, table, 0.0) for p in positions])
    assert len(ids) == len(positions)
    ordered = sorted(positions, reverse=True) + [0] * 5
    tiles = [-(-max(ordered[i:i + 2]) // TILE) for i in range(0, 16, 2)]
    assert r.context_slots["decode"] == {
        "slots_read": sum(tiles) * TILE * 2,
        "slots_valid": sum(positions), "slots_reach": sum(positions),
        "slots_full": 16 * 48}
    assert tiles[-2:] == [0, 0]  # the padded groups read nothing


@pytest.mark.parametrize("model,lanes", [("gpt2", 8), ("olmoe", 16),
                                         ("nemotron_h", 32)])
def test_one_program_a_bucket_as_before(small_tiles, model, lanes):
    """The three benchmark engines' settings at toy widths (8, 16 and 32
    lanes, chunked prefill, gpt2 with a verify window): warm-up compiles
    one program a bucket and kind, and serving lanes of every length
    compiles no other."""
    from ray_tpu.serve.llm.runner import DecodeItem

    kw = _toy(model)
    kw.update(max_batch_size=lanes, prefill_chunk_size=32, num_blocks=160,
              max_model_len=64)
    if model == "gpt2":
        kw["speculative"] = {"num_draft_tokens": 4}
    engine = LLMEngine(EngineConfig(**kw))
    r = engine.runner
    # prompts of 16 and 32, chunks of 16 and 32, decode 1, 2, 4, .. lanes
    buckets = 2 + 2 + lanes.bit_length() + (model == "gpt2")
    assert engine.warmup() == buckets
    table = list(range(1, 17))
    for n in (1, 3, lanes):
        r.decode([DecodeItem(1, 1 + (7 * i) % 60, table, 0.0, slot=i)
                  for i in range(n)])
    r.prefill_chunk([1] * 20, 24, table, 0.0)
    assert r.compiled_signatures() == buckets
