"""serve.llm tests: block pool, decode parity, scheduler preemption /
EOS, bounded recompilation, and the serve-deployment integration
(8 concurrent streamed requests, zero drops).

Decode parity is THE correctness gate: prefill + N single-token paged
decode steps must reproduce the full-sequence forward's logits (atol
1e-4, f32 tiny configs) for both model families — any drift in the
cache layout, rope positions, or masking shows up here first.
"""

import dataclasses
import math
import random
import sys
import threading

import cloudpickle
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm import (
    BlockPool,
    EngineConfig,
    LLMEngine,
    ModelRunner,
    SamplingParams,
    Scheduler,
    SeqState,
    Sequence,
)
from ray_tpu.serve.llm.cache import CacheExhausted
from ray_tpu.serve.llm.runner import DecodeItem, adapters
from ray_tpu.serve.llm.scheduler import DecodeWork, PrefillWork

cloudpickle.register_pickle_by_value(sys.modules[__name__])


# ------------------------------------------------------------- block pool


def test_block_pool_alloc_free_and_null_page():
    pool = BlockPool(num_blocks=8, block_size=4)
    assert pool.usable_blocks == 7  # page 0 reserved
    a = pool.alloc(3)
    assert 0 not in a and len(set(a)) == 3
    assert pool.num_free() == 4
    with pytest.raises(CacheExhausted):
        pool.alloc(5)
    assert pool.num_free() == 4  # all-or-nothing
    pool.free(a)
    assert pool.num_free() == 7
    with pytest.raises(ValueError):
        pool.free(a)  # double free
    assert pool.blocks_for_tokens(1) == 1
    assert pool.blocks_for_tokens(4) == 1
    assert pool.blocks_for_tokens(5) == 2


def test_block_pool_prefix_index_refcount_and_lru():
    """The tentpole's bookkeeping invariants: content-registered pages
    survive release in an LRU, match_prefix revives + refcounts them,
    shared pages outlive any single owner, and eviction recycles the
    coldest cached page first."""
    from ray_tpu.serve.llm.cache import chain_hashes

    pool = BlockPool(num_blocks=6, block_size=4)  # 5 usable
    toks = list(range(1, 13))  # 3 full pages worth
    h = chain_hashes(toks, 4, 3)
    assert h == chain_hashes(toks, 4, 3)  # deterministic
    assert h[:2] == chain_hashes(toks[:8] + [99, 98, 97, 96], 4, 3)[:2]

    a = pool.alloc(2)
    pool.register(a[0], h[0])
    pool.register(a[1], h[1])
    # a second sequence with the same prefix shares the pages
    m = pool.match_prefix(h[:2])
    assert m == a
    assert pool.refcount(a[0]) == 2
    pool.free(a)  # first owner leaves: pages stay pinned by the second
    assert pool.refcount(a[0]) == 1
    assert pool.num_cached() == 0
    pool.free(m)  # last ref: registered pages PARK, not free
    assert pool.num_cached() == 2
    assert pool.num_free() == 5  # still allocatable (evictable)
    assert pool.num_used() == 0

    # revival out of the LRU
    m2 = pool.match_prefix(h)  # 3rd hash unknown: partial match
    assert m2 == a and pool.num_cached() == 0
    pool.free(m2)

    # eviction order: coldest first, and a freed chain parks TAIL-first
    # so eviction shrinks a cached prefix from its tail, never orphaning
    # the pages behind a missing head. Allocate 4 of 5 usable pages —
    # the 3 truly-free pages go first, then the LRU's oldest (a[1]).
    b = pool.alloc(4)
    assert a[1] in b and a[0] not in b
    assert pool.evictions == 1
    # the chain HEAD survives: a fresh match still reuses the first
    # page and stops at the evicted tail
    m3 = pool.match_prefix(h[:2])
    assert m3 == [a[0]]
    # first-writer-wins: a[0] still owns h[0]; re-registering that hash
    # on another page is a no-op and the original stays matchable
    pool.register(b[0], h[0])
    assert pool.match_prefix([h[0]]) == [a[0]]
    pool.free([a[0]])  # ref from the h[:2] match
    pool.free([a[0]])  # ref from the [h[0]] match
    pool.free(b)


# ----------------------------------------------------------- decode parity


def _parity_case(name, cfg, forward):
    ad = adapters()[name]
    params = ad.init_fn(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    n_prompt, n_dec = 13, 8
    toks = rng.randint(1, cfg.vocab_size, size=n_prompt + n_dec).tolist()
    full = np.asarray(forward(params, jnp.asarray([toks], jnp.int32),
                              cfg))[0]
    runner = ModelRunner(ad, cfg, params, block_size=8, num_blocks=16,
                         max_model_len=32, max_batch_size=2)
    pool = BlockPool(16, 8)
    table = pool.alloc(pool.blocks_for_tokens(n_prompt))
    _, last = runner.prefill(toks[:n_prompt], table, 0.0)
    np.testing.assert_allclose(last, full[n_prompt - 1], atol=1e-4)
    # teacher-forced decode: feed the reference token at each position,
    # compare logits against the full-sequence forward at that position
    for t in range(n_prompt, n_prompt + n_dec):
        need = pool.blocks_for_tokens(t + 1)
        if len(table) < need:
            table += pool.alloc(need - len(table))
        _, logits = runner.decode([DecodeItem(toks[t], t, table, 0.0)])
        np.testing.assert_allclose(logits[0], full[t], atol=1e-4)


@pytest.fixture(params=["one-tile", "page-tiles"])
def context_tiles(request, context_tile_pages):
    """The context read as these toy rows make it (one tile holds the
    whole table), and in tiles of one page: positions 13..20 then read
    two and three of a table's four."""
    if request.param == "page-tiles":
        context_tile_pages(1)


def test_decode_parity_gpt2(context_tiles):
    from ray_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32,
                              remat=False)
    _parity_case("gpt2", cfg, gpt2.gpt2_forward)


def test_decode_parity_llama(context_tiles):
    from ray_tpu.models import llama

    _parity_case("llama", llama.LlamaConfig.tiny(), llama.llama_forward)


def test_llama_tiny_serves_the_logits_it_always_did():
    """The llama family's serve programs after the forwards were written
    over three helpers: prefill, a chunk from an offset and a decode step
    of the `tiny` preset give, in float32 on the CPU, the logits recorded
    from the commit before (PR 26's), within 1e-6, and no routing
    account."""
    ad = adapters()["llama"]
    cfg = ad.presets["tiny"]()
    params = ad.init_fn(jax.random.PRNGKey(0), cfg)
    r = ModelRunner(ad, cfg, params, block_size=4, num_blocks=16,
                    max_model_len=32, max_batch_size=2,
                    prefill_chunk_size=8)
    table = [3, 7, 2, 9]
    tok, last = r.prefill(list(range(1, 9)), table, 0.0)
    assert tok == 8
    np.testing.assert_allclose(
        last[:4], [0.12197931, 0.09360814, -0.06515012, 0.10851755],
        atol=1e-6, rtol=0)
    tok, last = r.prefill_chunk(list(range(9, 15)), 8, table, 0.0)
    assert tok == 14
    np.testing.assert_allclose(
        last[:4], [0.17846088, -0.032823294, 0.11180488, 0.051300142],
        atol=1e-6, rtol=0)
    toks, logits = r.decode([DecodeItem(tok, 14, table, 0.0)])
    assert toks == [14]
    np.testing.assert_allclose(
        logits[0, :4], [0.20625733, -0.05909551, 0.0822587, 0.07995838],
        atol=1e-6, rtol=0)
    assert r.take_expert_pairs() == []  # a dense model reports no routing


def test_decode_batch_parity_independent_sequences():
    """Batched decode lanes must not leak across sequences: two
    different prompts decoded in one batch match their solo runs."""
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    ad = adapters()["llama"]
    params = ad.init_fn(jax.random.PRNGKey(1), cfg)
    runner = ModelRunner(ad, cfg, params, block_size=4, num_blocks=32,
                         max_model_len=32, max_batch_size=4)
    pool = BlockPool(32, 4)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 9)]
    tables, nexts = [], []
    for p in prompts:
        t = pool.alloc(pool.blocks_for_tokens(len(p) + 1))
        nxt, _ = runner.prefill(p, t, 0.0)
        tables.append(t)
        nexts.append(nxt)
    batch = [DecodeItem(nexts[i], len(prompts[i]), tables[i], 0.0)
             for i in range(2)]
    joint_toks, joint_logits = runner.decode(batch)
    for i in range(2):
        solo_toks, solo_logits = runner.decode([batch[i]])
        assert joint_toks[i] == solo_toks[0]
        np.testing.assert_allclose(joint_logits[i], solo_logits[0],
                                   atol=1e-4)


# ---------------------------------------------------- bounded recompilation


def test_prefill_bucketing_bounds_compiles():
    from ray_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32,
                              remat=False)
    ad = adapters()["gpt2"]
    params = ad.init_fn(jax.random.PRNGKey(0), cfg)
    runner = ModelRunner(ad, cfg, params, block_size=8, num_blocks=64,
                         max_model_len=64, max_batch_size=4,
                         prefill_bucket_min=16)
    assert runner.prefill_bucket(3) == 16
    assert runner.prefill_bucket(17) == 32
    assert runner.prefill_bucket(64) == 64
    with pytest.raises(ValueError):
        runner.prefill_bucket(65)
    pool = BlockPool(64, 8)
    for n in (3, 5, 9, 14, 16):  # five lengths, ONE bucket
        table = pool.alloc(pool.blocks_for_tokens(n))
        runner.prefill(list(range(1, n + 1)), table, 0.0)
        pool.free(table)
    sigs = runner.compiled_signatures()
    assert sigs in (-1, 1), f"expected 1 compiled prefill program: {sigs}"


# ---------------------------------------------------------- scheduler unit


def _mk_seq(i, n_prompt, max_tokens=4):
    return Sequence(seq_id=i, prompt=list(range(1, n_prompt + 1)),
                    sampling=SamplingParams(max_tokens=max_tokens))


def test_scheduler_admission_waits_for_pages():
    pool = BlockPool(num_blocks=5, block_size=4)  # 4 usable pages
    sched = Scheduler(pool, max_batch_size=4, max_model_len=16)
    s1, s2 = _mk_seq(0, 12), _mk_seq(1, 12)  # 3 pages each
    sched.add(s1)
    sched.add(s2)
    w = sched.schedule()
    assert isinstance(w, PrefillWork) and w.seq is s1
    # s2 needs 3 pages, only 1 free: decode continues, no admission
    w2 = sched.schedule()
    assert isinstance(w2, DecodeWork) and w2.seqs == [s1]
    sched.commit_token(s1, 99)
    assert s1.state is SeqState.RUNNING
    # finishing s1 releases pages; s2 admits next
    sched._retire(s1, "test")
    w3 = sched.schedule()
    assert isinstance(w3, PrefillWork) and w3.seq is s2


def test_scheduler_preempts_lifo_and_requeues_front():
    pool = BlockPool(num_blocks=5, block_size=4)
    sched = Scheduler(pool, max_batch_size=4, max_model_len=16)
    s1, s2 = _mk_seq(0, 8, max_tokens=8), _mk_seq(1, 7, max_tokens=8)
    sched.add(s1)
    sched.add(s2)
    assert isinstance(sched.schedule(), PrefillWork)  # s1: 2 pages
    assert isinstance(sched.schedule(), PrefillWork)  # s2: 2 pages
    sched.commit_token(s1, 5)
    sched.commit_token(s2, 5)
    # s1 at pos 9 needs page 3; pool empty -> LIFO victim is s2
    w = sched.schedule()
    assert isinstance(w, DecodeWork)
    assert w.seqs == [s1]
    assert s2.state is SeqState.WAITING and s2.preemptions == 1
    assert sched.waiting[0] is s2  # requeued at the FRONT
    assert s2.table == []  # pages released
    assert s2.refill_tokens == s2.prompt + [5]  # resume keeps progress


# ---------------------------------------------------- scheduler chunking


def test_scheduler_chunks_interleave_with_decode():
    """A long prompt prefills in page-aligned chunks and continuation
    chunks ALTERNATE with decode steps — one admission can no longer
    monopolize consecutive engine steps."""
    pool = BlockPool(num_blocks=64, block_size=4)
    sched = Scheduler(pool, max_batch_size=4, max_model_len=64,
                      chunk_size=8)
    s1 = _mk_seq(0, 6, max_tokens=8)
    sched.add(s1)
    w = sched.schedule()
    assert isinstance(w, PrefillWork) and w.seq is s1
    assert (w.start, w.end, w.is_last) == (0, 6, True)  # fits one chunk
    sched.commit_token(s1, 42)  # decode-ready

    # a DISTINCT prompt (no shared prefix, so no pages get skipped)
    s2 = Sequence(seq_id=1, prompt=list(range(100, 124)),
                  sampling=SamplingParams(max_tokens=8))
    sched.add(s2)
    w = sched.schedule()  # admission is still prefill-first
    assert isinstance(w, PrefillWork) and w.seq is s2
    assert (w.start, w.end, w.is_last) == (0, 8, False)
    w = sched.schedule()  # decode slips in between chunks
    assert isinstance(w, DecodeWork) and w.seqs == [s1]
    sched.commit_token(s1, 43)
    w = sched.schedule()
    assert isinstance(w, PrefillWork) and (w.start, w.end) == (8, 16)
    w = sched.schedule()
    assert isinstance(w, DecodeWork) and w.seqs == [s1]
    sched.commit_token(s1, 44)
    w = sched.schedule()
    assert isinstance(w, PrefillWork) and (w.start, w.end) == (16, 24)
    assert w.is_last
    sched.commit_token(s2, 45)
    w = sched.schedule()  # both lanes decode together now
    assert isinstance(w, DecodeWork) and w.seqs == [s1, s2]


def _long_seq(i, n_prompt, max_tokens=8):
    """A prompt of its own token ids: no page is matched and skipped."""
    return Sequence(seq_id=i, prompt=list(range(1000 * i, 1000 * i + n_prompt)),
                    sampling=SamplingParams(max_tokens=max_tokens))


def _ready_lanes(sched, n):
    """`n` lanes admitted by one chunk each and decode-ready."""
    seqs = [_mk_seq(i, 4, max_tokens=64) for i in range(n)]
    for s in seqs:
        sched.add(s)
        assert isinstance(sched.schedule(), PrefillWork)
        sched.commit_token(s, 7)
    return seqs


def _shape(sched, work):
    """A decision as ("chunk", seq_id, start) or ("decode", seq_ids)."""
    if isinstance(work, PrefillWork):
        if work.is_last:
            sched.commit_token(work.seq, 7)
        return ("chunk", work.seq.seq_id, work.start)
    for s in work.seqs:
        sched.commit_token(s, 7)
    return ("decode", [s.seq_id for s in work.seqs])


@pytest.mark.parametrize("chunks_a, chunks_b, expected", [
    # two lanes prefilling beside two ready ones: two chunks, the oldest
    # lane's first, then ONE decode step of every ready lane
    (4, 4, [("chunk", 10, 8), ("chunk", 10, 16), ("decode", [0, 1]),
            ("chunk", 10, 24), ("chunk", 11, 8), ("decode", [0, 1, 10]),
            ("chunk", 11, 16), ("decode", [0, 1, 10]),
            ("chunk", 11, 24), ("decode", [0, 1, 10, 11])]),
    # the oldest ends inside the round: its second chunk is the next lane's
    (2, 4, [("chunk", 10, 8), ("chunk", 11, 8), ("decode", [0, 1, 10]),
            ("chunk", 11, 16), ("decode", [0, 1, 10]),
            ("chunk", 11, 24), ("decode", [0, 1, 10, 11])]),
])
def test_scheduler_round_gives_each_prefilling_lane_a_chunk(
        chunks_a, chunks_b, expected):
    pool = BlockPool(num_blocks=256, block_size=4)
    sched = Scheduler(pool, max_batch_size=8, max_model_len=128,
                      chunk_size=8)
    _ready_lanes(sched, 2)
    sched.add(_long_seq(10, 8 * chunks_a, max_tokens=64))
    sched.add(_long_seq(11, 8 * chunks_b, max_tokens=64))
    # admissions first, their first chunks back to back, then the decode
    # step that opens the round with two lanes prefilling
    assert [_shape(sched, sched.schedule()) for _ in range(3)] == [
        ("chunk", 10, 0), ("chunk", 11, 0), ("decode", [0, 1])]
    assert [_shape(sched, sched.schedule())
            for _ in expected] == expected
    d = sched.depth()
    assert d["multi_chunk_rounds"] == sum(  # a chunk behind a chunk
        a[0] == b[0] == "chunk" for a, b in zip(expected, expected[1:]))
    assert d["continuation_chunks"] == sum(
        e[0] == "chunk" for e in expected)


@pytest.mark.parametrize("newcomer", [None, 4, 16])
def test_scheduler_one_lane_prefilling_alternates_as_before(newcomer):
    """With at most one lane prefilling a round is one chunk and one
    decode step, an admission's first chunk being the round's chunk:
    the alternation the scheduler always had, decision for decision."""
    pool = BlockPool(num_blocks=256, block_size=4)
    sched = Scheduler(pool, max_batch_size=8, max_model_len=128,
                      chunk_size=8)
    _ready_lanes(sched, 2)
    sched.add(_long_seq(10, 32, max_tokens=64))
    got = [_shape(sched, sched.schedule()) for _ in range(4)]
    assert got == [("chunk", 10, 0), ("decode", [0, 1]),
                   ("chunk", 10, 8), ("decode", [0, 1])]
    if newcomer is None:
        expected = [("chunk", 10, 16), ("decode", [0, 1]),
                    ("chunk", 10, 24), ("decode", [0, 1, 10])]
    elif newcomer <= 8:
        # a newcomer of one chunk takes the round's chunk: the lane
        # prefilling waits a round, a decoding lane for one chunk
        sched.add(_long_seq(11, newcomer, max_tokens=64))
        expected = [("chunk", 11, 0), ("decode", [0, 1, 11]),
                    ("chunk", 10, 16), ("decode", [0, 1, 11]),
                    ("chunk", 10, 24), ("decode", [0, 1, 10, 11])]
    else:
        # a second lane prefilling: from the next decode step on, two
        # chunks a round
        sched.add(_long_seq(11, newcomer, max_tokens=64))
        expected = [("chunk", 11, 0), ("decode", [0, 1]),
                    ("chunk", 10, 16), ("chunk", 10, 24),
                    ("decode", [0, 1, 10]),
                    ("chunk", 11, 8), ("decode", [0, 1, 10, 11])]
    assert [_shape(sched, sched.schedule()) for _ in expected] == expected
    assert sched.depth()["multi_chunk_rounds"] == (newcomer == 16)


@pytest.fixture(scope="module")
def saturated_rounds():
    """A seeded closed loop through `schedule()` / `commit_token` alone:
    128 callers on 64 lanes, prompts of 1 to 32 chunks (lognormal, mean
    10.9, the lfm2 cell's), 256 output tokens each. Every decision is
    written down with the lanes that were prefilling when it was made."""
    rng = random.Random(50)
    lanes, chunk = 64, 4
    pool = BlockPool(num_blocks=lanes * 100 + 1, block_size=4,
                     enable_prefix_cache=False)
    sched = Scheduler(pool, max_batch_size=lanes, max_model_len=400,
                      chunk_size=chunk)
    chunks_drawn = []

    def call():
        n = min(32, max(1, math.ceil(rng.lognormvariate(math.log(8), 0.8))))
        chunks_drawn.append(n)
        sched.add(Sequence(seq_id=len(chunks_drawn), prompt=[1] * (n * chunk),
                           sampling=SamplingParams(max_tokens=256)))

    for _ in range(128):
        call()
    rounds = []  # a decode step: (continuation chunks before it, lanes
    # prefilling at the step before it, its ready lanes)
    since, prefilling = 0, None
    while len(rounds) < 1536:
        work = sched.schedule()
        if isinstance(work, PrefillWork):
            since += work.start > 0
            done = [work.seq] if work.is_last else []
        else:
            rounds.append((since, prefilling, len(work.seqs)))
            since = 0
            prefilling = sum(s.prefill_pending for s in sched.running)
            done = list(work.seqs)
        for s in done:
            if sched.commit_token(s, 7):
                call()
    return sched, rounds, chunks_drawn


def test_saturated_rounds_never_pass_the_lanes_prefilling(saturated_rounds):
    _, rounds, chunks_drawn = saturated_rounds
    assert 10.4 < sum(chunks_drawn) / len(chunks_drawn) < 11.4
    # before the first decode step nothing decodes and chunks go alone
    for since, prefilling, _ in rounds[1:]:
        assert since <= prefilling
    assert max(since for since, _, _ in rounds[1:]) > 1


def test_saturated_rounds_keep_the_lanes_decoding(saturated_rounds):
    sched, rounds, _ = saturated_rounds
    steady = [ready for _, _, ready in rounds[512:]]  # two answers in
    assert sum(steady) / len(steady) >= 0.85 * sched.max_batch_size
    assert sched.preemption_count == 0
    assert len(sched.running) == sched.max_batch_size


def test_scheduler_depth_counts_the_rounds(saturated_rounds):
    sched, rounds, _ = saturated_rounds
    d = sched.depth()
    assert d["decode_steps"] == len(rounds)
    assert d["decode_lanes"] == sum(ready for _, _, ready in rounds)
    assert d["continuation_chunks"] == sum(n for n, _, _ in rounds)
    assert d["multi_chunk_rounds"] == sum(n > 1 for n, _, _ in rounds)
    assert 0 < d["multi_chunk_rounds"] < d["decode_steps"]


# ------------------------------------------------------------ engine level


def _f32_engine(num_blocks, max_batch_size=4, seed=0, chunk=256,
                prefix_cache=True, max_model_len=32):
    from ray_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32,
                              remat=False)
    return LLMEngine(EngineConfig(
        model="gpt2", model_config=cfg, block_size=4,
        num_blocks=num_blocks, max_model_len=max_model_len,
        max_batch_size=max_batch_size, seed=seed,
        prefill_chunk_size=chunk, enable_prefix_cache=prefix_cache))


def _drive(engine, streams):
    import time

    deadline = time.monotonic() + 120
    while any(s.final() is None for s in streams):
        if not engine.step():
            pass
        assert time.monotonic() < deadline, "engine made no progress"
    return [s.final() for s in streams]


def test_engine_greedy_matches_model_teacher_forced():
    """ENGINE-level parity (not just runner-level): greedy engine
    output must equal the teacher-forced argmax of the full-sequence
    forward. This is the test that catches engine<->runner position
    convention bugs (e.g. feeding the last token at pos instead of
    pos-1), which runner-level parity cannot see."""
    from ray_tpu.models import gpt2

    eng = _f32_engine(num_blocks=64)
    prompt = list(range(1, 11))
    out = eng.generate(prompt, SamplingParams(max_tokens=8), drive=True)
    gen = out["token_ids"]
    cfg = eng.model_cfg
    toks = prompt + gen
    full = np.asarray(gpt2.gpt2_forward(
        eng.runner.params, jnp.asarray([toks], jnp.int32), cfg))[0]
    ref = [int(np.argmax(full[t][:cfg.vocab_size]))
           for t in range(len(prompt) - 1, len(toks) - 1)]
    assert gen == ref, (gen, ref)


def test_cache_exhaustion_preempts_and_completes_identically():
    """The acceptance gate: under a pool too small for both sequences,
    one gets preempted and STILL produces exactly the tokens it would
    have produced unpreempted (greedy, f32, recompute-style resume)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 500, size=10).tolist(),
               rng.randint(1, 500, size=11).tolist()]
    sp = SamplingParams(max_tokens=12)

    roomy = _f32_engine(num_blocks=64)
    want = [roomy.generate(p, sp, drive=True)["token_ids"]
            for p in prompts]

    tight = _f32_engine(num_blocks=11)  # 10 usable: forces preemption
    streams = [tight.add_request(p, sp) for p in prompts]
    finals = _drive(tight, streams)
    assert tight.scheduler.preemption_count > 0, \
        "pool was sized to force preemption"
    assert sum(f["preemptions"] for f in finals) > 0
    # the Prometheus counter must see them too (it increments around
    # schedule(), where preemption actually happens)
    from ray_tpu.util.metrics import prometheus_text

    line = [l for l in prometheus_text().splitlines()
            if l.startswith("serve_llm_preemptions_total{")]
    assert line and float(line[0].rsplit(" ", 1)[1]) > 0, line
    for f, expect in zip(finals, want):
        assert f["finish_reason"] == "length"
        assert f["token_ids"] == expect, \
            "preempted sequence diverged after requeue"


def test_eos_completion():
    eng = _f32_engine(num_blocks=64)
    free = eng.generate([7, 8, 9], SamplingParams(max_tokens=8),
                        drive=True)
    toks = free["token_ids"]
    assert len(toks) == 8 and free["finish_reason"] == "length"
    eos = toks[3]
    stopped = eng.generate(
        [7, 8, 9], SamplingParams(max_tokens=8, eos_token_id=eos),
        drive=True)
    assert stopped["finish_reason"] == "eos"
    # generation halts at the FIRST occurrence of the eos token
    first = toks.index(eos)
    assert stopped["token_ids"] == toks[:first + 1]


def test_chunked_prefill_parity_vs_monolithic():
    """Chunked prefill (page-aligned chunks via the prefill-from-offset
    program) must reproduce monolithic prefill bit-identically under
    greedy sampling, for both model families."""
    from ray_tpu.models import llama

    rng = np.random.RandomState(17)
    prompt = rng.randint(1, 500, size=21).tolist()
    sp = SamplingParams(max_tokens=8)

    mono = _f32_engine(num_blocks=64, chunk=0, prefix_cache=False)
    want = mono.generate(prompt, sp, drive=True)["token_ids"]
    chunked = _f32_engine(num_blocks=64, chunk=8, prefix_cache=False)
    got = chunked.generate(prompt, sp, drive=True)["token_ids"]
    assert got == want, "gpt2 chunked prefill diverged from monolithic"

    lcfg = llama.LlamaConfig.tiny()
    lp = rng.randint(1, lcfg.vocab_size, size=19).tolist()

    def llama_eng(chunk):
        return LLMEngine(EngineConfig(
            model="llama", model_config=lcfg, block_size=4,
            num_blocks=64, max_model_len=32, max_batch_size=4,
            prefill_chunk_size=chunk, enable_prefix_cache=False))

    lw = llama_eng(0).generate(lp, sp, drive=True)["token_ids"]
    lg = llama_eng(8).generate(lp, sp, drive=True)["token_ids"]
    assert lg == lw, "llama chunked prefill diverged from monolithic"


def test_prefix_cache_hit_parity_and_counters():
    """Warm-cache generation (prefix pages shared, prefill skipped) is
    bit-identical to the cold greedy run, and the hit/skip shows up in
    the engine's counters."""
    rng = np.random.RandomState(23)
    shared = rng.randint(1, 500, size=16).tolist()  # 4 full pages
    suffixes = [rng.randint(1, 500, size=3).tolist() for _ in range(3)]
    sp = SamplingParams(max_tokens=8)

    # cold references from per-prompt fresh engines (no reuse possible)
    want = [
        _f32_engine(num_blocks=96, chunk=8).generate(
            shared + sfx, sp, drive=True)["token_ids"]
        for sfx in suffixes]

    eng = _f32_engine(num_blocks=96, chunk=8)
    got, cached = [], []
    for sfx in suffixes:
        stream = eng.add_request(shared + sfx, sp)
        _drive(eng, [stream])
        fin = stream.final()
        got.append(fin["token_ids"])
        cached.append(fin["cached_tokens"])
    st = eng.stats()
    assert got == want, "warm prefix-cache output diverged from cold"
    # 2nd and 3rd requests each match the 4 shared full pages, and the
    # final event reports the reused tokens
    assert cached == [0, 16, 16], cached
    assert st["prefix_hit_pages"] >= 8, st
    assert st["blocks_used"] == 0  # all refs released
    assert st["blocks_cached"] > 0  # ...but pages parked for reuse
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    for name in ("serve_llm_prefix_cache_hits_total",
                 "serve_llm_prefix_cache_misses_total",
                 "serve_llm_prefill_chunks_total"):
        assert name in text, f"missing metric {name}"


def test_preemption_while_prefix_shared():
    """Two sequences share prefix pages; cache pressure preempts one.
    The victim's dropped refs must not invalidate the survivor's shared
    pages, and BOTH must finish bit-identical to an unconstrained run
    (the refcounting acceptance gate)."""
    rng = np.random.RandomState(29)
    shared = rng.randint(1, 500, size=12).tolist()  # 3 pages, 2 matchable
    prompts = [shared + rng.randint(1, 500, size=2).tolist(),
               shared + rng.randint(1, 500, size=3).tolist()]
    sp = SamplingParams(max_tokens=10)

    want = [
        _f32_engine(num_blocks=64, chunk=8).generate(
            p, sp, drive=True)["token_ids"] for p in prompts]

    tight = _f32_engine(num_blocks=10, chunk=8)  # 9 usable pages
    streams = [tight.add_request(p, sp) for p in prompts]
    finals = _drive(tight, streams)
    assert tight.scheduler.preemption_count > 0, \
        "pool was sized to force preemption under sharing"
    assert tight.scheduler.prefix_hit_pages > 0, \
        "second sequence should share the prefix pages"
    for f, expect in zip(finals, want):
        assert f["token_ids"] == expect, \
            "sharing + preemption changed greedy output"
    st = tight.stats()
    assert st["blocks_used"] == 0


def test_compile_misses_bounded_after_warmup():
    """The recompilation acceptance gate: after warmup() no request mix
    (short, long/chunked, warm-prefix, preempting) may trigger another
    XLA compile — serve_llm_compile_misses_total must not move."""
    from ray_tpu.util.metrics import prometheus_text

    def misses():
        total = 0.0
        for line in prometheus_text().splitlines():
            if line.startswith("serve_llm_compile_misses_total{"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    eng = _f32_engine(num_blocks=24, chunk=8, max_batch_size=2)
    eng.warmup()
    base = misses()
    rng = np.random.RandomState(31)
    shared = rng.randint(1, 500, size=10).tolist()
    sp = SamplingParams(max_tokens=6)
    for n in (3, 17, 25):  # one-chunk, multi-chunk, multi-chunk
        eng.generate(rng.randint(1, 500, size=n).tolist(), sp,
                     drive=True)
    for _ in range(2):  # warm-prefix path (prefill from offset)
        eng.generate(shared + rng.randint(1, 500, size=2).tolist(), sp,
                     drive=True)
    streams = [eng.add_request(
        rng.randint(1, 500, size=12).tolist(),
        SamplingParams(max_tokens=12)) for _ in range(2)]
    _drive(eng, streams)  # small pool: decode growth under pressure
    assert misses() == base, \
        "a request mix recompiled after warmup (unbounded programs)"


def test_topk_topp_sampling():
    """Satellite gate: top-k/top-p run in-jit. Degenerate settings
    reduce to greedy (bit-identical), and a top-k=2 stream only ever
    emits tokens from the greedy top-2 at each step."""
    from ray_tpu.models import gpt2

    prompt = list(range(1, 9))
    base = _f32_engine(num_blocks=64)
    want = base.generate(prompt, SamplingParams(max_tokens=6),
                         drive=True)["token_ids"]
    k1 = _f32_engine(num_blocks=64).generate(
        prompt, SamplingParams(max_tokens=6, temperature=1.0, top_k=1),
        drive=True)["token_ids"]
    assert k1 == want, "top_k=1 must reduce to greedy"
    p0 = _f32_engine(num_blocks=64).generate(
        prompt, SamplingParams(max_tokens=6, temperature=1.0,
                               top_p=1e-9), drive=True)["token_ids"]
    assert p0 == want, "top_p->0 must reduce to greedy"

    eng = _f32_engine(num_blocks=64, seed=7)
    out = eng.generate(prompt, SamplingParams(
        max_tokens=8, temperature=1.5, top_k=2), drive=True)
    cfg = eng.model_cfg
    toks = list(prompt)
    for tok in out["token_ids"]:
        full = np.asarray(gpt2.gpt2_forward(
            eng.runner.params, jnp.asarray([toks], jnp.int32), cfg))[0]
        logits = full[-1][:cfg.vocab_size]
        top2 = set(np.argsort(logits)[-2:].tolist())
        assert tok in top2, (tok, top2)
        toks.append(tok)


def test_truncation_cutoff_matches_sort_reference():
    """The sampler finds the top-k / top-p cutoff by bisection (a
    full-vocabulary sort costs ~20 s of TPU compile in every program);
    the sort stays here as the reference. Covers ties, disabled
    filters, k beyond the vocabulary, and top_p -> 0."""
    from ray_tpu.serve.llm.runner import truncation_cutoff

    def sort_cutoff(lg, temp, tk, tp):
        desc = -np.sort(-lg)
        kth = desc[min((tk if tk > 0 else len(lg)), len(lg)) - 1]
        z = desc.astype(np.float64) / temp
        p = np.exp(z - z.max())
        p /= p.sum()
        keep = (np.cumsum(p) - p) < tp
        return max(kth, desc[keep].min())

    rng = np.random.RandomState(0)
    S, V = 7, 640
    lg = (rng.normal(size=(S, V)) * 3).astype(np.float32)
    lg[1, :100] = lg[1, 0]  # a large tie group
    lg[2] = np.round(lg[2])  # ties everywhere
    lg[3, 500:] = -1e30  # masked vocabulary padding
    temps = np.array([1.0, 0.7, 1.5, 1.0, 2.0, 1.0, 0.5], np.float32)
    topks = np.array([5, 0, 17, 100000, 1, 0, 40], np.int32)
    topps = np.array([1.0, 0.9, 0.5, 0.7, 1.0, 1e-9, 0.95], np.float32)
    got = np.asarray(jax.jit(truncation_cutoff)(
        jnp.asarray(lg), jnp.asarray(temps)[:, None],
        jnp.asarray(topks), jnp.asarray(topps)))[:, 0]
    want = np.array([sort_cutoff(lg[i], temps[i], int(topks[i]),
                                 float(topps[i])) for i in range(S)],
                    np.float32)
    # same kept set: the cutoff is an attained logit, exactly
    np.testing.assert_array_equal(got, want)


def test_engine_concurrent_requests_zero_drops():
    """8 concurrent requests through one engine, interleaved prefill/
    decode, every request completes with its full token budget."""
    eng = _f32_engine(num_blocks=128, max_batch_size=8)
    rng = np.random.RandomState(11)
    lens = [3, 5, 7, 9, 11, 13, 15, 16]
    streams = [eng.add_request(rng.randint(1, 500, size=n).tolist(),
                               SamplingParams(max_tokens=6))
               for n in lens]
    finals = _drive(eng, streams)
    assert len(finals) == 8
    for f in finals:
        assert f["done"] and f["finish_reason"] == "length"
        assert f["num_generated"] == 6
    st = eng.stats()
    assert st["waiting"] == 0 and st["running"] == 0
    assert st["blocks_used"] == 0  # everything released


def test_metrics_exported():
    from ray_tpu.util.metrics import prometheus_text

    eng = _f32_engine(num_blocks=64)
    eng.generate([1, 2, 3], SamplingParams(max_tokens=3), drive=True)
    text = prometheus_text()
    for name in ("serve_llm_tokens_generated_total",
                 "serve_llm_requests_total", "serve_llm_ttft_ms",
                 "serve_llm_cache_utilization"):
        assert name in text, f"missing metric {name}"


# ------------------------------------------------------ serve integration


@pytest.fixture(scope="module")
def llm_cluster():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    serve.shutdown()
    ray_tpu.shutdown()
    c.shutdown()


def test_llm_deployment_8_concurrent_streams(llm_cluster):
    """The serving acceptance gate: >= 8 concurrent requests stream
    token-by-token through a serve deployment on CPU jax with zero
    dropped requests, and engine metrics surface via the state API."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    app = build_llm_app(
        model="gpt2", preset="tiny",
        engine_config={"block_size": 8, "num_blocks": 96,
                       "max_model_len": 64, "max_batch_size": 8},
        max_ongoing_requests=16)
    handle = serve.run(app, name="llm")
    try:
        sh = handle.options(stream=True, generator_backpressure=64)
        rng = np.random.RandomState(5)
        n_req, n_tok = 8, 5
        gens = [sh.remote({"prompt": rng.randint(1, 500, size=4 + i)
                           .tolist(),
                           "max_tokens": n_tok})
                for i in range(n_req)]

        results = [None] * n_req
        errors = []

        def consume(i, gen):
            try:
                events = [ray_tpu.get(r, timeout=120) for r in gen]
                results[i] = events
            except Exception as e:  # noqa: BLE001
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=consume, args=(i, g))
                   for i, g in enumerate(gens)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, f"dropped/errored requests: {errors}"
        for events in results:
            assert events is not None
            *toks, final = events
            assert len(toks) == n_tok  # one event per token, streamed
            assert [e["index"] for e in toks] == list(range(n_tok))
            assert final["done"] and final["finish_reason"] == "length"
            assert final["num_generated"] == n_tok

        from ray_tpu.util.state import llm_status

        stats = llm_status("llm")
        assert len(stats) == 1
        assert stats[0]["model"] == "gpt2"
        assert stats[0]["running"] == 0 and stats[0]["waiting"] == 0
    finally:
        serve.delete("llm")


def test_llm_deployment_streams_a_family_with_two_kinds_of_kv_layer(
        llm_cluster):
    """`serve.run` of the mimo_v2 family through the same LLMServer,
    engine, scheduler and runner: prompts of several windows stream
    token by token, the tokens are those of an engine driven in this
    process on the same seeded weights, and the replica's status carries
    the pools by kind, the window kind's pages given back on the way."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
        build_llm_app,
    )

    engine_config = {"block_size": 4, "num_blocks": 96, "max_model_len": 64,
                     "max_batch_size": 4, "prefill_chunk_size": 8}
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 500, size=n).tolist() for n in (5, 19, 33, 26)]
    local = LLMEngine(EngineConfig(model="mimo_v2", preset="tiny",
                                   **engine_config))
    want = [local.generate(p, SamplingParams(max_tokens=12), drive=True)
            ["token_ids"] for p in prompts]
    handle = serve.run(build_llm_app(
        model="mimo_v2", preset="tiny", engine_config=engine_config,
        max_ongoing_requests=8), name="llm-mimo")
    try:
        sh = handle.options(stream=True, generator_backpressure=64)
        gens = [sh.remote({"prompt": p, "max_tokens": 12}) for p in prompts]
        got = []
        for gen in gens:
            *toks, final = [ray_tpu.get(r, timeout=180) for r in gen]
            assert [e["index"] for e in toks] == list(range(12))
            assert final["done"] and final["finish_reason"] == "length"
            got.append(final["token_ids"])
        assert got == want

        from ray_tpu.util.state import llm_status

        stats, = llm_status("llm-mimo")
        assert stats["model"] == "mimo_v2"
        assert stats["running"] == 0 and stats["waiting"] == 0
        assert list(stats["kv"]) == ["full", "window"]
        assert stats["kv"]["window"]["released_behind_window"] > 0
        assert stats["kv"]["window"]["pages_used"] == 0
        assert stats["kv"]["window"]["largest_table"] <= 5
    finally:
        serve.delete("llm-mimo")


def test_affinity_routing_concentrates_shared_prefix(llm_cluster):
    """Prefix-affinity routing: requests sharing a prompt prefix carry
    the same affinity key, rendezvous onto ONE of two replicas, and
    that replica's prefix cache serves the shared pages — the hits show
    up on exactly one engine."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app, prompt_affinity_key

    app = build_llm_app(
        model="gpt2", preset="tiny", num_replicas=2,
        engine_config={"block_size": 8, "num_blocks": 96,
                       "max_model_len": 64, "max_batch_size": 8,
                       "prefill_chunk_size": 16},
        max_ongoing_requests=16)
    handle = serve.run(app, name="llm-aff")
    try:
        rng = np.random.RandomState(9)
        shared = rng.randint(1, 500, size=24).tolist()  # 3 full pages
        for _ in range(4):
            p = shared + rng.randint(1, 500, size=2).tolist()
            sh = handle.options(stream=True,
                                affinity_key=prompt_affinity_key(p))
            events = [ray_tpu.get(r, timeout=120)
                      for r in sh.remote({"prompt": p, "max_tokens": 3})]
            assert events[-1]["done"]

        from ray_tpu.util.state import llm_status

        stats = llm_status("llm-aff")
        assert len(stats) == 2
        hits = [s.get("prefix_hit_pages", 0) for s in stats]
        # 3 warm requests x 3 shared pages, all on the SAME replica
        assert sum(hits) >= 9, stats
        assert max(hits) == sum(hits), \
            f"affinity routing scattered a shared prefix: {hits}"
    finally:
        serve.delete("llm-aff")
