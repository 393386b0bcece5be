"""The glm_dsa family (`GlmDsaConfig.tiny`: latent attention whose rows
choose 16 slots by a learned indexer, one leading dense feed-forward, then
16 sigmoid-routed SwiGLU experts of which 4 are held beside a shared one)
against the plain reference the benchmark compares with on the chip
(`benchmark/reference_glm_5.py`), on seeded random weights, and what its
latent kind of KV layer asks of the serve engine: a latent row and an
indexer-key row a token under one block table, the choice inside the
cached-context read.

Logits are compared, not sampled tokens, at contexts several times the 16
slots a row may choose, so that most slots are refused. TOL: system and
reference do the same float32 arithmetic in another order (absorbed
against up-projected, tiles under a mask against one full score matrix),
which moves a logit of magnitude 0.1-0.6 by under 1e-6 here; 2e-5 leaves
room for a platform's reduction order, and every mutation of
`test_each_mechanism_shows` moves the logits past it by an order of
magnitude or more."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_glm_5 as ref
from ray_tpu.models import glm_dsa as gd
from ray_tpu.models.glm_dsa import GlmDsaConfig, init_glm_dsa
from ray_tpu.ops import context_attention as ca
from ray_tpu.serve.llm import cache
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-5
CFG = GlmDsaConfig.tiny()
K = CFG.index_topk
T = 80  # five times the slots a row may choose


def _arch(cfg):
    return {**{k: getattr(cfg, k) for k in ref.ARCH_KEYS if hasattr(cfg, k)},
            "rope_interleave": True, "indexer_rope_interleave": True,
            "rope_theta": cfg.rope_theta}


ARCH = _arch(CFG)


def _seeded(cfg, seed=7):
    p = init_glm_dsa(jax.random.PRNGKey(seed), cfg)
    # norm scales away from 1 and a bias away from 0, so that one left
    # out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("attn_norm", "ffn_norm", "q_norm", "kv_norm",
                     "ik_norm"):
            layer[name] = 1.0 + 0.2 * jax.random.normal(
                next(keys), layer[name].shape)
        layer["ik_bias"] = 0.2 * jax.random.normal(
            next(keys), layer["ik_bias"].shape)
    p["lnf"] = 1.0 + 0.2 * jax.random.normal(next(keys), p["lnf"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (T,), 1, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), ARCH)[0])


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 2 pages (8 slots) a layer, so that a context of 80 slots
    is ten tiles and the groups of a decode step reach different ones."""
    monkeypatch.setattr(cache, "TILE_ELEMENTS_A_LAYER", 8 * 16 // 3 + 1)


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, cfg=CFG, **kw):
    args = dict(block_size=4, num_blocks=64, max_model_len=96,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["glm_dsa"], cfg, params, **args)


def _engine(**overrides):
    kw = dict(model="glm_dsa", preset="tiny", block_size=4, num_blocks=96,
              max_model_len=96, max_batch_size=4, prefill_chunk_size=16,
              seed=0)
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _serve(engine, prompts, n, logprobs=False):
    streams = [engine.add_request(list(p), SamplingParams(
        max_tokens=k, temperature=0.0, logprobs=logprobs))
        for p, k in zip(prompts, n)]
    for _ in range(4000):
        if not engine.has_work():
            break
        engine.step()
    return [s.final() for s in streams]


# ---------------------------------------------------- against the reference


def test_whole_forward_matches_the_reference(params, tokens, want):
    got = gd.glm_dsa_prefill_kv(params, jnp.asarray(tokens)[None], CFG)[0][0]
    assert np.abs(want).max() > 0.1
    assert _worst(got, want) < TOL


def test_the_reference_in_row_blocks_is_the_reference(params, tokens, want,
                                                      monkeypatch):
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    ref._layer.clear_cache()
    got = ref.forward(params, jnp.asarray(tokens), ARCH)[0]
    ref._layer.clear_cache()
    assert _worst(got, want) < 1e-6


def test_chunks_then_decode_through_the_latent_pool(params, tokens, want,
                                                    small_tiles):
    """Chunks of 16 rows through the paged pool, each choosing among the
    cached slots and its own rows together, then decode steps: every
    program's last row against the reference's full forward."""
    r = _runner(params)
    assert r.layouts[0].tile_pages == 2
    table = list(range(1, 1 + -(-T // 4)))
    n = 64
    for s in range(0, n, 16):
        _, logits = r.prefill_chunk(tokens[s:s + 16].tolist(), s, table, 0.0)
        assert _worst(logits, want[s + 15]) < TOL, s
    for pos in range(n, T):
        _, logits = r.decode([DecodeItem(int(tokens[pos]), pos, table, 0.0)])
        assert _worst(logits[0], want[pos]) < TOL, pos


def test_decode_lanes_of_unlike_lengths_in_groups(params, small_tiles):
    """Eight lanes at lengths from 3 to 75 in one decode program (groups
    of 2 rows, longest first): each lane's logits are its own sequence's
    reference."""
    r = _runner(params, max_batch_size=8, num_blocks=256)
    rng = np.random.default_rng(3)
    lengths = [75, 3, 40, 17, 64, 9, 33, 52]
    items, wants, at = [], [], 1
    for n in lengths:
        seq = rng.integers(1, CFG.vocab_size, n + 1)
        table = list(range(at, at + -(-(n + 1) // 4)))
        at += len(table)
        for s in range(0, n, 16):
            r.prefill_chunk(seq[s:min(n, s + 16)].tolist(), s, table, 0.0)
        items.append(DecodeItem(int(seq[n]), n, table, 0.0))
        wants.append(np.asarray(ref.forward(
            params, jnp.asarray(seq), ARCH)[0])[n])
    _, logits = r.decode(items)
    for i, w in enumerate(wants):
        assert _worst(logits[i], w) < TOL, lengths[i]
    by = r.context_by_kind["latent"]["decode"]
    assert by["slots_scored"] >= by["slots_valid"] >= by["slots_selected"]
    assert by["slots_valid"] == sum(lengths)
    assert by["slots_selected"] == sum(min(n, K) for n in lengths)
    # the latent tiles are read as far as the indexer keys, under the mask
    assert by["slots_read"] == by["slots_scored"]


def test_absorbed_path_equals_the_up_projected_path(params, tokens):
    """A prompt's own rows up-projected (the prompt program) against the
    same rows absorbed on their latents (a chunk from position 0 with
    nothing cached)."""
    r = _runner(params, prefill_chunk_size=32, max_model_len=64)
    table = list(range(1, 9))
    _, up = r.prefill(tokens[:32].tolist(), table, 0.0)
    _, absorbed = r.prefill_chunk(tokens[:32].tolist(), 0, table, 0.0)
    assert _worst(up, absorbed) < TOL


def test_rotation_is_over_interleaved_pairs():
    """Lanes (2i, 2i + 1) are one complex number turned by t *
    theta^(-2i/width); lanes past the width are untouched."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 12))
    pos = jnp.asarray([0, 1, 7, 30, 200])
    got = np.asarray(gd._rope(x, pos, 1e4, 8))
    z = np.asarray(x[..., 0:8:2]) + 1j * np.asarray(x[..., 1:8:2])
    angle = np.asarray(pos, np.float64)[:, None, None] \
        * 1e4 ** (-2.0 * np.arange(4) / 8)
    turned = z * np.exp(1j * angle)
    np.testing.assert_allclose(got[..., 0:8:2], turned.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1:8:2], turned.imag, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x[..., 8:]))
    # the reference's, written apart, agrees (positions 0..T-1)
    y = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 12))
    np.testing.assert_allclose(
        np.asarray(gd._rope(y, jnp.arange(6), 1e4, 8)),
        np.asarray(ref._rotate(y, 1e4, 8)), atol=1e-6)


def test_the_chosen_set_is_the_references(params, tokens):
    """Layer 0's indexer on the reference's normed rows: the program's
    mask (index scores, `select_mask`) is the reference's choice, slot
    for slot, and every row past the 16th refuses slots."""
    p = params["layers"][0]
    x = params["wte"][jnp.asarray(tokens)]
    h = ref._rmsnorm(x, p["attn_norm"], CFG.rms_norm_eps)
    _, theirs = ref.attention_half(h, p, ARCH)
    pos = jnp.arange(T)[None]
    *_, qi, ki, w = gd._projections(h[None], p, pos, CFG)
    seen = jnp.tril(jnp.ones((T, T), bool))[None]
    ours = ca.select_mask(ca.index_scores(qi, ki, w, seen), K)[0]
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    chosen = np.asarray(ours).sum(-1)
    np.testing.assert_array_equal(chosen, np.minimum(np.arange(T) + 1, K))


def test_the_choice_is_exact_and_breaks_ties_by_the_earliest():
    scores = jnp.asarray([[3.0, 1.0, 2.0, 2.0, 2.0, ca.MASKED, 5.0, 2.0]])
    got = np.asarray(ca.select_mask(scores, 4))[0]
    # 5, 3, then the two earliest of the four 2s; the masked slot never
    np.testing.assert_array_equal(
        got, [True, False, True, True, False, False, True, False])
    few = jnp.asarray([[1.0, ca.MASKED, 0.5, ca.MASKED, ca.MASKED, ca.MASKED]])
    np.testing.assert_array_equal(
        np.asarray(ca.select_mask(few, 4))[0],
        [True, False, True, False, False, False])


@pytest.mark.parametrize("mutation", [
    "no_selection", "topk_8", "indexer_not_rotated", "indexer_half_split",
    "no_relu", "k_pe_not_rotated", "score_scale_nope", "no_shared_expert",
    "no_routed_scale"])
def test_each_mechanism_shows(params, tokens, want, mutation):
    """The reference with one mechanism changed differs from the
    reference by far more than TOL: the comparison above would catch the
    program doing the same."""
    arch = {
        "no_selection": {**ARCH, "index_topk": None},
        "topk_8": {**ARCH, "index_topk": 8},
        "indexer_not_rotated": {**ARCH, "indexer_rope_interleave": "none"},
        "indexer_half_split": {**ARCH, "indexer_rope_interleave": False},
        "no_relu": {**ARCH, "index_relu": False},
        "k_pe_not_rotated": {**ARCH, "k_pe_rotated": False},
        "score_scale_nope": {**ARCH, "score_width": CFG.qk_nope_head_dim},
        "no_shared_expert": {**ARCH, "n_shared_experts": 0},
        "no_routed_scale": {**ARCH, "routed_scaling_factor": 1.0},
    }[mutation]
    got = ref.forward(params, jnp.asarray(tokens), arch)[0]
    assert _worst(got, want) > 10 * TOL


def test_every_share_of_the_experts_sums_to_the_uncut_layer():
    """The four shares of 4 experts each, the shared expert counted once,
    add up to the layer with all 16 held: program and reference."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = _seeded(whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size))
    full, _ = gd._experts(h, p, whole)
    shared = gd._swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"],
                        jnp.float32)
    parts, theirs = 0, 0
    for offset in range(0, 16, 4):
        cut = dataclasses.replace(CFG, expert_offset=offset)
        held = {**p, **{n: p[n][offset:offset + 4]
                        for n in ("we_gate", "we_up", "we_down")}}
        parts = parts + gd._experts(h, held, cut)[0] - shared
        theirs = theirs + ref.ffn_half(
            h, held, True, {**ARCH, "expert_offset": offset})[0] - shared
    assert _worst(parts + shared, full) < TOL
    assert _worst(theirs + shared, full) < TOL
    assert float(jnp.abs(shared).max()) > 100 * TOL


def test_the_cut_has_the_parameters_the_issue_counted():
    """Layer 0 (dense) and four expert layers with 8 of 256 experts and
    an eighth of the vocabulary: ISSUE 40's 2,701.6 M parameters (its
    terms rounded to 0.1 M each; 2,701.67 M to the parameter), as many as
    the seeded tree holds but for the head's and the embedding's
    padding."""
    cfg = GlmDsaConfig.glm_5_l5_ep32()
    assert cfg.n_params() == 2_701_673_216
    assert abs(cfg.n_params() / 1e6 - 2701.6) < 0.1
    tree = jax.eval_shape(lambda k: init_glm_dsa(k, cfg),
                          jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    padding = 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.hidden_size
    assert held == cfg.n_params() + padding
    with open(ref._CONFIG) as f:
        config = json.load(f)
    assert config["parameters"] == cfg.n_params()
    for key, value in config["published"].items():
        if key not in config["reduced_keys"] and hasattr(cfg, key):
            assert getattr(cfg, key) == value, key


def test_importing_the_family_builds_nothing():
    assert not [n for n, v in vars(gd).items() if isinstance(v, jax.Array)]


# ------------------------------------------------------ through the engine


def test_the_adapter_describes_a_latent_kind():
    (kind,) = adapters()["glm_dsa"].kv_kinds(CFG)
    # 24 latent lanes, 8 rotated, 8 of padding; at GLM-5 512 + 64 + 64
    assert kind == cache.KVKind("latent", 3, 1, 40, 16, None, 16)
    lay = cache.KVLayout.of(kind, 8, 4)
    assert lay.shape == (3, 8, 4, 40) and lay.v_shape == (3, 8, 4, 16)
    assert lay.token_bytes(2) == {"latent": 240, "index": 96}
    big = cache.KVLayout.of(cache.KVKind(*GlmDsaConfig.glm_5_l5_ep32()
                                         .kv_kinds()[0]), 8, 16)
    assert big.token_bytes(2) == {"latent": 6400, "index": 1280}
    assert big.tile_pages == 64


def test_served_logprobs_are_the_references(params, small_tiles):
    """Requests of 20 to 70 tokens through the engine (chunks, lanes in
    one decode program, the pool shared): the log-probs of the eight
    tokens each streamed are the reference's."""
    engine = _engine()
    engine.update_weights(1, params)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
               for n in (70, 20, 45, 33)]
    finals = _serve(engine, prompts, [8] * 4, logprobs=True)
    cases = [{"prompt": p, "tokens": f["token_ids"]}
             for p, f in zip(prompts, finals)]
    wants = ref.serve_reference(params, None, cases, arch=ARCH)
    for f, w in zip(finals, wants):
        assert len(f["token_ids"]) == 8
        np.testing.assert_allclose(f["logprobs"], w, atol=2e-4)
    stats = engine.stats()
    assert list(stats["kv"]) == ["latent"]
    assert stats["kv"]["latent"]["select"] == K
    assert stats["kv"]["latent"]["token_bytes"] == {"latent": 480,
                                                    "index": 192}
    assert stats["kv"]["latent"]["pages_used"] == 0
    for program in ("decode", "prefill"):
        by = stats["context_by_kind"]["latent"][program]
        assert by["slots_scored"] >= by["slots_valid"] \
            >= by["slots_selected"] > 0
        assert by["slots_selected"] < by["slots_valid"]


def test_a_prefix_is_taken_on_the_latent_kind(params):
    """A latent page and its indexer keys depend on the prefix alone: the
    second request takes the first's pages and streams the same tokens."""
    engine = _engine()
    engine.update_weights(1, params)
    prompt = np.random.default_rng(6).integers(1, CFG.vocab_size, 50).tolist()
    first = _serve(engine, [prompt], [6])[0]
    second = _serve(engine, [prompt], [6])[0]
    assert first["token_ids"] == second["token_ids"]
    kv = engine.stats()["kv"]["latent"]
    assert kv["prefix_taken"] == 1 and kv["prefix_declined"] == 0


def test_speculation_is_refused_when_the_engine_is_built():
    with pytest.raises(ValueError, match="latent kind"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def test_the_counters_reach_the_metrics_page(params):
    from ray_tpu.util.metrics import prometheus_text
    from ray_tpu.util.watchtower import parse_prometheus

    engine = _engine()
    engine.update_weights(1, params)
    _serve(engine, [list(range(1, 41))], [4])
    series = {dict(tags).get("what") for (name, tags), n in
              parse_prometheus(prometheus_text()).items()
              if name == "serve_llm_ctx_slots_total"
              and dict(tags).get("model") == "glm_dsa" and n > 0}
    assert {"slots_scored", "slots_selected", "slots_valid"} <= series


# --------------------------------------------- the other families' programs


with open(os.path.join(os.path.dirname(__file__), "data",
                       "serve_hlo_pr37.json")) as _f:
    HLO_AT_THE_PARENT = json.load(_f)


@pytest.mark.parametrize("which", sorted(HLO_AT_THE_PARENT))
def test_mimo_v2s_programs_lower_as_before(which):
    """mimo_v2's prefill, chunk and decode programs at its tiny preset
    lower to the StableHLO they lowered to before a kind could choose its
    slots (gpt2's, llama's and nemotron_h's are held by
    tests/test_mimo_v2.py). Recorded at PR 37's commit by this very
    function, and anew at PR 56, whose programs take a launch's host
    arguments as one array (tests/test_mimo_v2.py says what holds their
    bodies)."""
    assert _lowered_mimo()[which] == HLO_AT_THE_PARENT[which]


def _lowered_mimo(_cache={}):
    if _cache:
        return _cache
    adapter = adapters()["mimo_v2"]
    cfg = adapter.presets["tiny"]()
    params = jax.eval_shape(
        lambda k: adapter.resident_fn(adapter.init_fn(k, cfg), cfg),
        jax.random.PRNGKey(0))
    r = ModelRunner(adapter, cfg, params, block_size=4, num_blocks=16,
                    max_model_len=32, max_batch_size=4, prefill_chunk_size=8)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    kp = tuple(S(lay.shape, cfg.dtype) for lay in r.layouts)
    vp = tuple(S(lay.v_shape, cfg.dtype) for lay in r.layouts)
    ids = S((4,), i32)

    def host(kind, bucket):  # the launch's pack, by its length
        return S((r._layout(kind, bucket)[0],), i32)

    texts = {
        "prefill": jax.jit(r._prefill_impl).lower(
            params, kp, vp, ids, {}, host("prefill", 8)),
        "chunk": jax.jit(r._chunk_impl).lower(
            params, kp, vp, ids, {}, host("chunk", 8)),
        "decode": jax.jit(r._decode_impl).lower(
            params, kp, vp, ids, {}, host("decode", 4)),
    }
    _cache.update({
        "mimo_v2." + name: hashlib.sha256(re.sub(
            r' \{jax\.result_info = "[^"]*"\}', "",
            low.as_text()).encode()).hexdigest()
        for name, low in texts.items()})
    return _cache


# ---- the benchmark's layer parity (benchmark/parity_glm_5.py), tiny


def _parity(params, tokens):
    from benchmark import parity_glm_5 as parity
    return parity.layer_parity(params, tokens, CFG, ARCH, chunk=16, page=4)


def test_layer_parity_reads_rounding_on_a_sound_program(params, tokens,
                                                        small_tiles):
    """Chunks of 16 through a permuted table, then the last 40 rows as
    decode steps of eight lanes, at contexts of 40 to 79 slots of which
    a row may choose 16: float32 on both sides, so every leg reads
    rounding and the choices are the reference's."""
    sound = _parity(params, tokens)
    assert sound["index_select"] == sound["decode_select"] == 0.0
    assert sound["routing"] == 0.0
    assert max(sound.values()) < 1e-4, sound


@pytest.mark.parametrize("fault", ["another_lanes_table", "one_slot_off",
                                   "one_lane_a_row_short"])
def test_layer_parity_sees_a_fault_in_the_decode_steps_read(
        params, tokens, small_tiles, monkeypatch, fault):
    """A fault that only a decode step's read has, in one lane of eight
    and so in a few rows of eighty, moves the decode rows' own legs: the
    legs over all rows (a 90th percentile, a mean) are not what holds
    it."""
    real = ca.attend_selected

    def faulty(q, latent, qi, ki, w, own_valid, ctx, *args, **kw):
        if q.shape[1] == 1 and q.shape[0] > 1:  # a decode step's lanes
            if fault == "another_lanes_table":  # lane 2 reads by lane 3's
                ctx = dataclasses.replace(ctx, tables=ctx.tables.at[2].set(
                    ctx.tables[3]))
            elif fault == "one_slot_off":  # lane 4's pages looked up one on
                ctx = dataclasses.replace(ctx, tables=ctx.tables.at[4].set(
                    jnp.roll(ctx.tables[4], 1)))
            else:  # lane 6 is taken for one slot shorter than it is
                ctx = dataclasses.replace(ctx, lengths=ctx.lengths.at[6].add(
                    -1))
        return real(q, latent, qi, ki, w, own_valid, ctx, *args, **kw)

    monkeypatch.setattr(gd, "attend_selected", faulty)
    from benchmark import parity_glm_5 as parity
    parity._program_rows.clear_cache()
    try:
        got = _parity(params, tokens)
    finally:
        parity._program_rows.clear_cache()
    assert max(got["decode_mixer"], got["decode_select"]) > 1e-2, got
