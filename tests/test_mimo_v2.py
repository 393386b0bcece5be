"""The mimo_v2 family (`MimoV2Config.tiny`: full and window attention
layers in the order full, window, window, full, window, a window of 8 with
sinks, K heads of 24 and V heads of 16, a leading dense feed-forward, then
16 sigmoid-routed SwiGLU experts of which 4 are held) against the plain
reference the benchmark compares with on the chip
(`benchmark/reference_mimo_v2.py`), on seeded random weights, and what its
two kinds of KV layer ask of the serve engine: a pool and a block table a
kind, the window kind's pages given back behind the window.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (tiles and a running softmax
against one full-length score matrix; the sink as the softmax's start
against one more column), which moves a logit of magnitude 0.1-0.5 by
under 1e-6 here; 2e-5 leaves room for a platform's reduction order, and
every mutation of `test_each_mechanism_shows` moves the logits past it by
an order of magnitude or more."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity_mimo_v2 as parity
from benchmark import reference_mimo_v2 as ref
from ray_tpu.models.mimo_v2 import MimoV2Config, init_mimo_v2
from ray_tpu.serve.llm.cache import BlockPool, KVKind, KVLayout, KVPools
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters
from ray_tpu.serve.llm.scheduler import PrefillWork, Scheduler, Sequence

TOL = 2e-5
CFG = MimoV2Config.tiny()
W = CFG.sliding_window


def _arch(cfg):
    keys = {k: getattr(cfg, k) for k in ref.ARCH_KEYS
            if k not in ("num_hidden_layers",)}
    return {**keys, "num_hidden_layers": cfg.n_layer}


ARCH = _arch(CFG)


def _seeded(cfg, seed=7):
    p = init_mimo_v2(jax.random.PRNGKey(seed), cfg)
    # norm scales away from 1, so that one left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("attn_norm", "ffn_norm"):
            layer[name] = 1.0 + 0.2 * jax.random.normal(
                next(keys), layer[name].shape)
    p["lnf"] = 1.0 + 0.2 * jax.random.normal(next(keys), p["lnf"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def tokens():  # six windows long
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (48,), 1, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), ARCH)[0])


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, **kw):
    args = dict(block_size=4, num_blocks=24, max_model_len=64,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["mimo_v2"], CFG, params, **args)


def _engine(**overrides):
    kw = dict(model="mimo_v2", preset="tiny", block_size=4, num_blocks=96,
              max_model_len=64, max_batch_size=4, prefill_chunk_size=8,
              seed=0)
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _serve(engine, prompts, n, logprobs=False, **sampling):
    streams = [engine.add_request(list(p), SamplingParams(
        max_tokens=k, temperature=0.0, logprobs=logprobs, **sampling))
        for p, k in zip(prompts, n)]
    for _ in range(4000):
        if not engine.has_work():
            break
        engine.step()
    return [s.final() for s in streams]


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, n).tolist() for n in lengths]


# ------------------------------------------------ the family and its kinds


def test_the_adapter_says_which_kinds_of_kv_layer_the_family_has():
    ad = adapters()["mimo_v2"]
    full, window = ad.kv_kinds(CFG)
    assert full == KVKind("full", 2, 2, 24, 16, None)
    assert window == KVKind("window", 3, 4, 24, 16, 8)
    assert ad.held_experts(CFG) == (4, 4) and ad.state_fn is None
    # one kind, K as wide as V, for every other family
    for name in ("gpt2", "llama", "nemotron_h"):
        other = adapters()[name]
        kinds = other.kv_kinds(other.presets["tiny"]())
        assert len(kinds) == 1 and kinds[0].window is None
        assert kinds[0].head_dim == kinds[0].v_head_dim


def test_the_presets_keep_the_published_widths():
    full = MimoV2Config.v2_5()
    assert (full.n_layer, full.hybrid_layer_pattern.count(0),
            full.hybrid_layer_pattern.count(1)) == (48, 9, 39)
    assert full.hybrid_layer_pattern[:12] == (0, 1, 1, 1, 1, 0,
                                              1, 1, 1, 1, 1, 0)
    assert full.moe_layer_freq == (0,) + (1,) * 47
    cut = MimoV2Config.v2_5_l7_ep16()
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "swa_num_key_value_heads", "head_dim", "v_head_dim",
                "rope_theta", "swa_rope_theta", "sliding_window",
                "attention_value_scale", "intermediate_size",
                "moe_intermediate_size", "n_routed_experts",
                "num_experts_per_tok", "partial_rotary_factor"):
        assert getattr(cut, key) == getattr(full, key), key
    assert cut.rotary_dim == 64 and cut.n_layer == 7
    assert cut.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert (cut.experts_held, cut.vocab_size) == (16, 19072)
    kinds = adapters()["mimo_v2"].kv_kinds(cut)
    assert [(k.layers, k.n_kv_head * k.head_dim, k.n_kv_head * k.v_head_dim)
            for k in kinds] == [(2, 768, 512), (5, 1536, 1024)]


def test_layout_of_a_kind_with_its_own_k_and_v_rows():
    lay = KVLayout.of(KVKind("window", 5, 8, 192, 128, 128), 896, 16)
    assert (lay.row, lay.v_row) == (1536, 1024)
    assert lay.shape == (5, 896, 16, 1536)
    assert lay.v_shape == (5, 896, 16, 1024)
    assert lay.block_bytes(2) == 16 * 25600
    # the 127 slots before a program's first row lie in 9 pages at most
    assert lay.window_pages == 9
    # window + a chunk of 256, wherever the pages' edges fall
    assert lay.lane_pages(256) == 25 and lay.lane_pages(1) == 9
    k, v = KVLayout(2, 3, 4, 2, 24, 16).zeros(jnp.float32)
    assert k.shape == (2, 3, 4, 48) and v.shape == (2, 3, 4, 32)


# ------------------------------------------------ against the reference


def test_whole_prompt_prefill_gives_the_references_logits(params, tokens,
                                                          want):
    from ray_tpu.models.mimo_v2 import mimo_v2_prefill_kv

    logits, k, v, counts = mimo_v2_prefill_kv(params, tokens[None], CFG)
    assert _worst(logits[0], want) < TOL
    assert [a.shape for a in k] == [(2, 1, 48, 2, 24), (3, 1, 48, 4, 24)]
    assert [a.shape for a in v] == [(2, 1, 48, 2, 16), (3, 1, 48, 4, 16)]
    chosen = ref.forward(params, jnp.asarray(tokens), ARCH)[1]
    assert counts.shape == (4, 16)
    np.testing.assert_array_equal(
        np.asarray(counts),
        [np.bincount(np.asarray(c).ravel(), minlength=16) for c in chosen])


@pytest.mark.parametrize("chunk", [4, 8, 16, 24])
def test_chunked_prefill_gives_the_references_logits(params, tokens, want,
                                                     chunk):
    """Chunks smaller than, equal to and larger than the window, through
    the runner's chunk program and both pools; every chunk's last row."""
    r = _runner(params, prefill_chunk_size=chunk)
    table = [list(range(1, 13)), list(range(1, 13))]
    for start in range(0, 48, chunk):
        _, last = r.prefill_chunk(tokens[start:start + chunk].tolist(),
                                  start, table, 0.0)
        assert _worst(last, want[start + chunk - 1]) < TOL


def test_prefill_then_decode_through_the_engine(params, tokens, want):
    """A prompt of three windows in chunks of one, then decode to six
    windows, with the window kind's pages given back on the way: the
    log-prob of every streamed token is the reference's."""
    engine = _engine()
    engine.update_weights(1, params)
    got, = _serve(engine, [tokens[:24]], [24], logprobs=True)
    seq = list(tokens[:24]) + got["token_ids"]
    logits = ref.forward(params, jnp.asarray(seq, jnp.int32), ARCH)[0]
    logp = np.asarray(ref.log_softmax(logits, CFG.vocab_size))
    wanted = [logp[24 - 1 + j, t] for j, t in enumerate(got["token_ids"])]
    assert np.max(np.abs(np.asarray(got["logprobs"]) - wanted)) < TOL
    kv = engine.stats()["kv"]
    assert kv["window"]["released_behind_window"] > 0
    assert kv["full"]["released_behind_window"] == 0


MUTATIONS = {
    "no_window": {"sliding_window": None},
    "no_sink": {"add_swa_attention_sink_bias": False},
    "no_value_scale": {"attention_value_scale": 1.0},
    "score_width_16": {"score_width": 16},
    "offset_one_on": {"expert_offset": 5},
    "rotate_every_dimension": {"partial_rotary_factor": 1.0},
    "one_theta": {"swa_rope_theta": 1e7},
    "window_one_less": {"sliding_window": W - 1},
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_each_mechanism_shows(params, tokens, want, name):
    """The reference with one mechanism changed is far outside TOL: the
    comparison above would see the program do the same."""
    wrong = ref.forward(params, jnp.asarray(tokens),
                        {**ARCH, **MUTATIONS[name]})[0]
    assert _worst(wrong, want) > 10 * TOL


def test_the_shares_add_up_to_the_whole_expert_layer(params):
    """The four shares of the tiny router's 16 experts, each computing its
    own experts' part with the program's expert layer, add up to what the
    reference gives when it holds all 16."""
    from ray_tpu.models.mimo_v2 import _experts

    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = init_mimo_v2(jax.random.PRNGKey(3), whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (40, CFG.hidden_size))
    want, chosen = ref.ffn_half(h, p, True, {**ARCH, "expert_offset": 0})
    total, pairs = 0.0, []
    for offset in range(0, 16, 4):
        share = dataclasses.replace(CFG, experts_held=4, expert_offset=offset)
        mine = {**p, **{n: p[n][offset:offset + 4]
                        for n in ("we_gate", "we_up", "we_down")}}
        y, counts = _experts(h, mine, share)
        total = total + y
        pairs.append(np.asarray(counts))
    assert _worst(total, want) < TOL
    # every share routes over all 16 and counts the same pairs
    assert all((c == pairs[0]).all() for c in pairs)
    np.testing.assert_array_equal(
        pairs[0], np.bincount(np.asarray(chosen).ravel(), minlength=16))


def test_layer_parity_reads_rounding_and_sees_each_control(params, tokens):
    """The benchmark's second leg at the tiny size: every half-layer of
    the program (chunks through a pool of its kind, released pages as the
    null page, then a decode step) against the reference's on the same
    normed rows; sound readings are rounding, each control is not."""
    rows = list(tokens[:41])  # five chunks of 8 and a decode step
    sound = parity.layer_parity(params, rows, CFG, ARCH, chunk=8, page=4)
    assert set(sound) == set(parity.READINGS)
    assert max(sound.values()) < 1e-5
    for name, key in [("no_window", "mixer_window"),
                      ("no_sink", "mixer_window"),
                      ("no_value_scale", "mixer_full"),
                      ("score_width_16", "mixer_full"),
                      ("rotate_every_dimension", "mixer_full"),
                      ("offset_one_on", "ffn_experts")]:
        wrong = parity.layer_parity(params, rows, CFG,
                                    {**ARCH, **MUTATIONS[name]}, chunk=8,
                                    page=4)
        assert wrong[key] > 1e-3, (name, wrong)
    low = parity.layer_parity(params, rows, CFG, ARCH, chunk=8, page=4,
                              operand_dtype=jnp.float8_e4m3fn)
    assert min(low[k] for k in parity.READINGS[:4]) > 1e-2


# ------------------------------------------- tables by kind and the release


def test_a_window_table_never_exceeds_its_bound():
    """The scheduler alone, planning a long prompt in chunks and then
    decoding: the window kind's table holds at most `lane_pages` pages at
    every plan, everything behind the window is the null page, and every
    page comes back when the sequence ends."""
    kinds = adapters()["mimo_v2"].kv_kinds(CFG)
    lay = KVLayout.of(kinds[1], 0, 4)
    pools = KVPools(kinds, [BlockPool(64, 4), BlockPool(16, 4)])
    sched = Scheduler(pools, max_batch_size=2, max_model_len=128,
                      chunk_size=8)
    seq = Sequence(0, list(range(1, 61)), SamplingParams(max_tokens=30))
    sched.add(seq)
    live = []
    while True:
        work = sched.schedule()
        if work is None:
            break
        first = work.start if isinstance(work, PrefillWork) \
            else seq.pos + seq.inflight - 1
        table = work.tables[1] if isinstance(work, PrefillWork) \
            else work.tables[0][1]
        gone = max(0, first - W + 1) // 4
        assert table[:gone] == [0] * gone and 0 not in table[gone:]
        live.append(len(table) - gone)
        rows = work.end - work.start if isinstance(work, PrefillWork) else 1
        assert live[-1] <= lay.lane_pages(rows)
        if isinstance(work, PrefillWork) and not work.is_last:
            continue
        if sched.commit_token(seq, 7):
            break
    assert max(live) == lay.lane_pages(8) - 1  # chunks start on a page
    stats = pools.stats()
    assert stats["window"]["largest_table"] == max(live)
    assert stats["window"]["released_behind_window"] > 15
    assert stats["full"]["largest_table"] == 23  # 89 positions
    assert stats["window"]["pages_used"] == stats["full"]["pages_used"] == 0


def _logits_by_request(engine, prompts, n):
    """Serve `prompts`, keeping every streamed token's log-prob."""
    out = _serve(engine, prompts, n, logprobs=True)
    return [(f["token_ids"], f["logprobs"]) for f in out]


def test_released_pages_are_reused_without_changing_a_logit(params):
    """Four lanes on a window pool so small that pages given back by one
    lane are written by another while both run, against a pool so large
    that no page is ever used twice: the same tokens and log-probs to the
    bit, and no preemption in either."""
    prompts = _prompts(1, (40, 9, 33, 21))
    n = [16, 24, 12, 20]

    def run(window_pages):
        engine = _engine(num_blocks=[96, window_pages])
        engine.update_weights(1, params)
        got = _logits_by_request(engine, prompts, n)
        return got, engine.stats()

    small, s_stats = run(22)
    large, l_stats = run(400)
    assert small == large
    assert s_stats["preemptions"] == l_stats["preemptions"] == 0
    kv = s_stats["kv"]["window"]
    assert kv["released_behind_window"] > kv["pages_total"]  # reused
    assert kv["largest_table"] <= 5 and kv["pages_used"] == 0
    assert l_stats["kv"]["window"]["released_behind_window"] \
        == kv["released_behind_window"]


def test_exhaustion_of_the_window_pool_alone_preempts_and_recovers(params):
    """A window pool that cannot hold the lanes it let in once their
    tables reach window + chunk (the full pool has room to spare; a lane
    is let in where the pool has ONE lane's bound free, which is no
    reservation): a lane is preempted, recomputed, and every request ends
    with the tokens it gets alone."""
    prompts = _prompts(2, (31, 30, 29, 28))
    n = [12, 12, 12, 12]
    alone = []
    for p, k in zip(prompts, n):
        engine = _engine()
        engine.update_weights(1, params)
        alone.append(_serve(engine, [p], [k])[0]["token_ids"])
    engine = _engine(num_blocks=[200, 10])
    engine.update_weights(1, params)
    got = _serve(engine, prompts, n)
    stats = engine.stats()
    assert [f["token_ids"] for f in got] == alone
    assert all(f["finish_reason"] == "length" for f in got)
    assert stats["preemptions"] > 0
    assert stats["kv"]["full"]["pages_used"] == 0
    assert stats["kv"]["window"]["pages_used"] == 0
    assert stats["kv"]["full"]["pages_total"] == 199


def test_a_preempted_sequence_continues_as_if_uninterrupted(params):
    """Preempted by hand mid-decode: the recompute (its whole history as
    a new prompt, the window kind's pages given back again on the way)
    continues with the tokens of the uninterrupted run."""
    prompt, = _prompts(3, (19,))
    engine = _engine()
    engine.update_weights(1, params)
    want, = _serve(engine, [prompt], [20])
    engine = _engine()
    engine.update_weights(1, params)
    stream = engine.add_request(prompt, SamplingParams(max_tokens=20))
    for _ in range(9):
        engine.step()
    with engine._step_lock:
        engine._drain("preempt")
        seq, = engine.scheduler.running
        assert 0 < len(seq.generated) < 20
        engine.scheduler.preempt(seq)
    while engine.has_work():
        engine.step()
    assert stream.final()["token_ids"] == want["token_ids"]
    assert stream.final()["preemptions"] == 1


def test_a_repeated_prompt_is_served_with_no_prefix_match(params):
    """Prefix reuse asked for, a family with a window kind: no match is
    looked up, every admission is counted as declined, and the second
    serving of a prompt equals the first."""
    prompt, = _prompts(4, (30,))
    engine = _engine(enable_prefix_cache=True)
    engine.update_weights(1, params)
    first, = _serve(engine, [prompt], [8])
    again, = _serve(engine, [prompt], [8])
    assert again["token_ids"] == first["token_ids"]
    assert again["cached_tokens"] == 0
    kv = engine.stats()["kv"]
    assert kv["full"]["prefix_declined"] == 2
    assert kv["full"]["prefix_taken"] == 0
    assert engine.stats()["prefix_hit_pages"] == 0
    # a family without a window kind takes its matches, and counts them
    other = LLMEngine(EngineConfig(
        model="gpt2", preset="tiny", block_size=4, num_blocks=64,
        max_model_len=64, max_batch_size=2, prefill_chunk_size=8))
    _serve(other, [prompt], [4])
    _serve(other, [prompt], [4])
    kv = other.stats()["kv"]
    assert list(kv) == ["full"]
    assert kv["full"]["prefix_taken"] == 1
    assert kv["full"]["prefix_declined"] == 0


def test_speculation_is_refused_when_the_engine_is_built():
    with pytest.raises(ValueError, match="window attention"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def test_stats_and_metrics_by_kind(params):
    """`stats()["kv"]`, `stats()["context_by_kind"]` and the
    `serve_llm_kv_*{kind=}` series after one request."""
    from ray_tpu.util.metrics import prometheus_text
    from ray_tpu.util.watchtower import parse_prometheus

    engine = _engine()
    engine.update_weights(1, params)
    _serve(engine, _prompts(5, (33,)), [12])
    stats = engine.stats()
    assert list(stats["kv"]) == ["full", "window"]
    assert stats["kv"]["window"]["window"] == W
    by = stats["context_by_kind"]
    total = stats["context"]["decode"]
    for what in ("slots_read", "slots_valid", "slots_reach", "slots_full"):
        assert by["full"]["decode"][what] + by["window"]["decode"][what] \
            == total[what]
    # a full kind sees every slot up to the length; a window kind W - 1
    assert by["full"]["decode"]["slots_valid"] \
        == by["full"]["decode"]["slots_reach"]
    assert by["window"]["decode"]["slots_valid"] == 11 * (W - 1)
    assert by["window"]["decode"]["slots_reach"] \
        == by["full"]["decode"]["slots_reach"]
    series = {(name, dict(tags).get("kind")): n for (name, tags), n in
              parse_prometheus(prometheus_text()).items()
              if name.startswith("serve_llm_kv_")
              and dict(tags).get("model") == "mimo_v2"}
    assert series[("serve_llm_kv_released_total", "window")] \
        >= stats["kv"]["window"]["released_behind_window"]
    assert ("serve_llm_kv_pages_used", "full") in series
    assert ("serve_llm_kv_largest_table", "window") in series


# --------------------------------------------- the other families' programs


with open(os.path.join(os.path.dirname(__file__), "data",
                       "serve_hlo_pr33.json")) as _f:
    HLO_AT_THE_PARENT = json.load(_f)


@pytest.mark.parametrize("which", sorted(HLO_AT_THE_PARENT))
def test_the_other_families_programs_lower_as_before(which):
    """gpt2's, OLMoE's (llama) and nemotron_h's prefill, chunk and decode
    programs at their tiny presets lower to the StableHLO they lowered to
    before a model had kinds of KV layer, windows and sinks (recorded at
    PR 33's commit by this very function; source locations are not in the
    text, and the results' pytree paths, which name no operation, are
    dropped). The six `prefill` and `chunk` hashes were recorded anew at
    PR 37, because those programs changed by design: they take one page
    id a group of `block_size` rows in place of (block ids, offsets) and
    store their rows a page at a time (pages of 4 and chunks of 8 here:
    two whole pages). All nine were recorded anew at PR 56, by `_lowered`
    as it stands, because every program's ARGUMENTS changed by design:
    the eight to ten host arrays of a launch are one, the pack
    (`runner.pack_layout`), sliced in the program's first lines. That the
    bodies below those lines give what they gave is held to the bit by
    tests/test_packed_launch.py, on results pinned before the change."""
    family, program = which.split(".")
    assert _lowered(family)[program] == HLO_AT_THE_PARENT[which]


_PRESETS = {"gpt2": "tiny", "llama": "olmoe_tiny", "nemotron_h": "tiny"}


def _lowered(family, _cache={}):
    if family in _cache:
        return _cache[family]
    adapter = adapters()[family]
    cfg = adapter.presets[_PRESETS[family]]()
    params = jax.eval_shape(
        lambda k: adapter.resident_fn(adapter.init_fn(k, cfg), cfg),
        jax.random.PRNGKey(0))
    r = ModelRunner(adapter, cfg, params, block_size=4, num_blocks=16,
                    max_model_len=32, max_batch_size=4, prefill_chunk_size=8)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    pool = S(r.layouts[0].shape, cfg.dtype)
    ids = S((4,), i32)
    state = jax.tree.map(lambda a: S(a.shape, a.dtype), r.state)

    def host(kind, bucket):  # the launch's pack, by its length
        return S((r._layout(kind, bucket)[0],), i32)

    texts = {
        "prefill": jax.jit(r._prefill_impl).lower(
            params, pool, pool, ids, state, host("prefill", 8)),
        "chunk": jax.jit(r._chunk_impl).lower(
            params, pool, pool, ids, state, host("chunk", 8)),
        "decode": jax.jit(r._decode_impl).lower(
            params, pool, pool, ids, state, host("decode", 4)),
    }
    _cache[family] = {
        name: hashlib.sha256(re.sub(
            r' \{jax\.result_info = "[^"]*"\}', "",
            low.as_text()).encode()).hexdigest()
        for name, low in texts.items()}
    return _cache[family]
