"""The lfm2 family (`Lfm2Config.tiny`: gated short convolutions and
attention blocks in the order ``c c A c c A c``, two dense SwiGLUs then
routed experts, 4 of 8 held) against the plain reference the benchmark
compares with on the chip (`benchmark/reference_lfm2.py`), on seeded
random weights, and what its conv windows ask of the serve engine.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (a carried window and a cached
context against one full pass), which moves a logit of magnitude 0.1-1.3
by under 1e-6 here; 2e-5 leaves room for a platform's reduction order, and
every mutation measured (`test_each_mechanism_shows`) moves the logits
past it by a factor of a hundred at least."""

import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lfm2 as ref
from ray_tpu.models import lfm2, moe
from ray_tpu.models.lfm2 import Lfm2Config, init_lfm2
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-5
CFG = Lfm2Config.tiny()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_arch = ref.arch_of
ARCH = _arch(CFG)


def _seeded(cfg, seed=7):
    p = init_lfm2(jax.random.PRNGKey(seed), cfg)
    # norm scales away from 1, so that one left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("operator_norm", "ffn_norm", "q_norm", "k_norm"):
            if name in layer:
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    next(keys), layer[name].shape)
    p["embedding_norm"] = 1.0 + 0.2 * jax.random.normal(
        next(keys), p["embedding_norm"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (80,), 1, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), ARCH)[0])


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, cfg=CFG, **kw):
    args = dict(block_size=8, num_blocks=24, max_model_len=64,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["lfm2"], cfg, params, **args)


def _engine(**overrides):
    kw = dict(model="lfm2", preset="tiny", block_size=4, num_blocks=96,
              max_model_len=48, max_batch_size=4, prefill_chunk_size=8,
              seed=0)
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _state(runner):
    return jax.tree.map(np.asarray, runner.state)


def test_the_adapter_says_what_the_family_caches():
    ad = adapters()["lfm2"]
    assert [(k.layers, k.n_kv_head, k.head_dim) for k in ad.kv_kinds(CFG)] \
        == [(2, 2, 16)] and CFG.n_layer == 7
    layers, parts = ad.state_fn(CFG)
    assert layers == 5
    assert [(n, s) for n, s, _ in parts] == [("conv0", (64,)),
                                             ("conv1", (64,))]
    cut = Lfm2Config.lfm2_8b_a1b_ep4()
    # the conv rows in the compute dtype, no float32 part (`assumed`)
    assert {jnp.dtype(d) for _, _, d in cut.state_parts()} \
        == {jnp.dtype(jnp.bfloat16)}
    # most layers carry state, few carry keys and values
    assert ad.state_fn(cut)[0] == 18 and ad.kv_kinds(cut)[0].layers == 6
    assert ad.held_experts(cut) == (0, 8)


def test_the_published_preset_is_the_published_model():
    """Every key of the catalog row's `config` (kept under `published` in
    the benchmark's configuration file), 8.34 B parameters by count of
    shapes, no array made; the cut changes what `reduced` lists only."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    full = Lfm2Config.lfm2_8b_a1b()
    published = dict(config["published"])
    assert published.pop("model_type") == "lfm2_moe"
    assert published.pop("conv_bias") is False  # the family has no key
    assert published.pop("num_hidden_layers") == full.n_layer == 24
    assert tuple(published.pop("layer_types")) == full.layer_types
    for key, value in published.items():
        assert getattr(full, key) == value, key
    assert (full.n_conv_layers, full.n_kv_layers, full.n_expert_layers) \
        == (18, 6, 22)
    assert full.head_dim * full.num_attention_heads == full.hidden_size

    def count(cfg):
        shapes = jax.eval_shape(lambda: init_lfm2(jax.random.PRNGKey(0), cfg))
        assert {a.dtype for a in jax.tree.leaves(shapes)} \
            == {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}  # the bias
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))

    assert count(full) == 8_339_930_560  # ISSUE 44's 8.34 B
    cut = Lfm2Config.lfm2_8b_a1b_ep4()
    assert count(cut) == 2_425_961_920  # ISSUE 44's 2.426 B: 4.85 GB
    same = {f.name for f in dataclasses.fields(cut)} - {
        "experts_held", "vocab_size", "max_position_embeddings"}
    assert all(getattr(cut, f) == getattr(full, f) for f in same)
    # the cut as the configuration file states it
    assert (config["num_experts"], config["vocab_size"],
            config["max_position_embeddings"]) == (
        cut.experts_held, cut.vocab_size, cut.max_position_embeddings)
    assert all(config[k] == v for k, v in config["published"].items()
               if k not in ("num_experts", "vocab_size",
                            "max_position_embeddings"))


def test_importing_the_family_builds_nothing():
    assert "lfm2_8b_a1b_ep4" in adapters()["lfm2"].presets
    assert isinstance(lfm2._LAYERS_8B_A1B, tuple)


def test_whole_prompt_prefill_matches_the_reference(params, tokens, want):
    for n in (16, 13):  # a full bucket, and one with padded rows
        r = _runner(params)
        _, last = r.prefill(tokens[:n].tolist(), [3, 7], 0.0)
        assert _worst(last, want[n - 1]) < TOL


@pytest.mark.parametrize("chunk,tile_pages", [(8, None), (16, None),
                                              (32, None), (8, 1), (16, 2)])
def test_chunked_prefill_then_decode_match_the_reference(
        params, tokens, want, chunk, tile_pages, context_tile_pages):
    """A prompt of 37 tokens in chunks of `chunk` rows (the last program
    padded), the conv window carried in the lane's slot from chunk to
    chunk, then four decode steps. The attention layers read the cached
    context as these toy rows make it (one tile holds the table), and in
    tiles of one and two pages."""
    if tile_pages:
        context_tile_pages(tile_pages)
    r = _runner(params, prefill_chunk_size=chunk)
    table = [3, 7, 2, 9, 5, 11]
    n, at = 37, 0
    while at < n:
        end = min(n, at + chunk)
        _, last = r.collect(r.launch_chunk(
            tokens[at:end].tolist(), at, table, 0.0, slot=2))
        at = end
    assert _worst(last, want[n - 1]) < TOL
    for pos in range(n, n + 4):
        _, logits = r.decode([DecodeItem(int(tokens[pos]), pos, table, 0.0,
                                         slot=2)])
        assert _worst(logits[0], want[pos]) < TOL


def test_chunks_of_uneven_length_equal_the_prompt_in_one_piece(params,
                                                               tokens):
    """The carried window: 8 + 16 + 5 rows (the last padded to 8) through
    the chunk program and 29 rows through one program (3 padded) leave
    the first conv layer's window equal to the bit and the deeper ones'
    to rounding, and give the same logits."""
    a = _runner(params, prefill_chunk_size=16)
    b = _runner(params, prefill_chunk_size=32)
    table = [3, 7, 2, 9]
    for at, end in ((0, 8), (8, 24), (24, 29)):
        _, last_a = a.collect(a.launch_chunk(
            tokens[at:end].tolist(), at, table, 0.0, slot=1))
    _, last_b = b.collect(b.launch_prefill(tokens[:29].tolist(), table, 0.0,
                                           slot=1))
    assert _worst(last_a, last_b) < TOL
    sa, sb = _state(a), _state(b)
    for name in ("conv0", "conv1"):
        np.testing.assert_array_equal(sa[name][0], sb[name][0])
        np.testing.assert_allclose(sa[name], sb[name], atol=1e-6)
        assert np.abs(sa[name][:, 1]).max() > 0
        assert (np.delete(sa[name], 1, axis=1) == 0).all()


def test_engine_logprobs_match_the_reference(params, tokens):
    """Prefill (chunked), then decode through the engine, overlapped loop
    and all: the streamed log-probs against the reference's one full
    forward over prompt + streamed tokens."""
    e = LLMEngine(EngineConfig(
        model="lfm2", preset="tiny", block_size=8, num_blocks=24,
        max_model_len=64, max_batch_size=4, prefill_chunk_size=16),
        params=params)
    prompt = tokens[:21].tolist()
    out = e.generate(prompt, SamplingParams(max_tokens=6, logprobs=True),
                     drive=True)
    seq = jnp.asarray(prompt + out["token_ids"], jnp.int32)
    logp = np.asarray(ref.log_softmax(ref.forward(params, seq, ARCH)[0],
                                      CFG.vocab_size))
    ref_lp = [logp[20 + j, t] for j, t in enumerate(out["token_ids"])]
    assert _worst(out["logprobs"], ref_lp) < TOL


def test_the_uncut_model_matches_the_uncut_reference(tokens):
    """Every expert held, from offset 0: the reference runs uncut too."""
    whole = dataclasses.replace(CFG, experts_held=8, expert_offset=0)
    p = _seeded(whole)
    want = np.asarray(ref.forward(p, jnp.asarray(tokens[:24]),
                                  _arch(whole))[0])
    r = _runner(p, cfg=whole, prefill_chunk_size=32)
    _, last = r.prefill(tokens[:24].tolist(), [3, 7, 2], 0.0)
    assert _worst(last, want[23]) < TOL


MUTATIONS = {
    "the conv taps in another order":
        lambda a: {**a, "taps": "reversed"},
    "no selection bias": lambda a: {**a, "use_expert_bias": False},
    "no renormalisation": lambda a: {**a, "norm_topk_prob": False},
    "one expert fewer a token":
        lambda a: {**a, "num_experts_per_tok": a["num_experts_per_tok"] - 1},
    "the held range one expert on":
        lambda a: {**a, "expert_offset": a["expert_offset"] + 1},
    "another rotation base": lambda a: {**a, "rope_theta": 1e4},
    "the q and k norms left out": lambda a: {**a, "norms": "ones"},
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_each_mechanism_shows(params, tokens, want, mutation):
    """The reference made wrong in one way moves the logits far past TOL:
    a tolerance that passes the system fails each of these."""
    arch = MUTATIONS[mutation](ARCH)
    p = params
    if arch.pop("taps", None):
        p = {**params, "layers": [
            {**q, "conv_w": q["conv_w"][::-1]} if "conv_w" in q else q
            for q in params["layers"]]}
    if arch.pop("norms", None):
        p = {**params, "layers": [
            {**q, **{n: jnp.ones_like(q[n]) for n in ("q_norm", "k_norm")}}
            if "q_norm" in q else q for q in params["layers"]]}
    got = np.asarray(ref.forward(p, jnp.asarray(tokens), arch)[0])
    assert _worst(got, want) > 100 * TOL, mutation


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer: each of four chips holds 2 of the router's 8
    experts; their routed parts add up to what the uncut reference gives
    for the whole layer (none is shared, so nothing is counted once). The
    program is given each share in turn through its own `_experts`."""
    whole = dataclasses.replace(CFG, experts_held=8, expert_offset=0)
    p = _seeded(whole)["layers"][3]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        uncut, chosen = ref.feed_forward(h, p32, True, _arch(whole))
    total, counts = 0.0, []
    for chip in range(4):
        share = dataclasses.replace(CFG, experts_held=2,
                                    expert_offset=2 * chip)
        part = {**p, **{n: p[n][2 * chip:2 * chip + 2]
                        for n in ("we_gate", "we_up", "we_down")}}
        y, c = lfm2._experts(h, part, share)
        total = total + y
        counts.append(np.asarray(c))
    assert _worst(total, uncut) < TOL
    # the router's load is the model's, whatever is held
    assert all((c == counts[0]).all() for c in counts)
    assert counts[0].sum() == 24 * CFG.num_experts_per_tok
    np.testing.assert_array_equal(
        counts[0], np.bincount(np.asarray(chosen).ravel(), minlength=8))


def test_selection_with_the_bias_and_weights_without_it_by_hand():
    """Two rows over four experts, worked by hand: scores sigmoid(logit);
    the bias lifts expert 3 into every row's choice; the weights are the
    scores without it over their sum + 1e-6."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    router = jnp.asarray([[2.0, 0.0, -2.0, -1.0], [0.0, 1.0, 3.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])
    w, e, counts, s = moe.route(x, router, 2, True, score="sigmoid",
                                select_bias=bias, norm_eps=1e-6)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    # row 0: scores .881 .5 .119 .269; with the bias expert 3 reads 1.269
    # row 1: scores .5 .731 .953 .047; with the bias expert 3 reads 1.047
    np.testing.assert_array_equal(np.asarray(e), [[3, 0], [3, 2]])
    want = np.asarray([[sig(-1.0), sig(2.0)], [sig(-3.0), sig(3.0)]])
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)
    assert not np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-7, rtol=0)
    np.testing.assert_array_equal(np.asarray(counts), [1, 0, 1, 2])
    # without the bias expert 3 is no row's choice
    _, e, _, _ = moe.route(x, router, 2, True, score="sigmoid")
    np.testing.assert_array_equal(np.asarray(e), [[0, 1], [2, 1]])


@pytest.mark.parametrize("family", ["olmoe", "nemotron_h", "mimo_v2",
                                    "glm_dsa"])
def test_routed_experts_lowers_to_the_same_program_without_norm_eps(family):
    """`routed_experts` as the four families call it today, with
    `route`'s new argument left out or at its default: the same HLO."""
    x = jnp.zeros((8, 32), jnp.bfloat16)
    router = jnp.zeros((32, 8), jnp.bfloat16)
    bias = jnp.zeros((8,), jnp.float32)
    wg = jnp.zeros((4, 32, 16), jnp.bfloat16)
    wd = jnp.zeros((4, 16, 32), jnp.bfloat16)
    how = {"olmoe": dict(k=2, norm_topk=False),
           "nemotron_h": dict(k=2, norm_topk=True, score="sigmoid",
                              select_bias=bias, scale=2.5, held=(2, 4),
                              shared=lambda rows: rows),
           "mimo_v2": dict(k=2, norm_topk=True, score="sigmoid",
                           select_bias=bias, scale=1.0, held=(2, 4)),
           "glm_dsa": dict(k=2, norm_topk=True, score="sigmoid",
                           select_bias=bias, scale=2.5, held=(0, 4),
                           shared=lambda rows: rows)}[family]
    if family == "olmoe":
        wg, wd = jnp.zeros((8, 32, 16), x.dtype), jnp.zeros((8, 16, 32),
                                                            x.dtype)

    def fn(rows, mm):
        return mm(jax.nn.silu(mm(rows, wg)), wd)

    def plain(x):
        return moe.routed_experts(x, router, fn, **how)

    def spelled(x):
        return moe.routed_experts(x, router, fn, **how, norm_eps=0.0)

    assert jax.jit(plain).lower(x).as_text() \
        == jax.jit(spelled).lower(x).as_text().replace("spelled", "plain")


def test_padded_rows_and_idle_lanes_leave_a_window_bit_equal(params, tokens):
    """A program without a slot (warm-up) writes nothing; a decode step
    leaves every slot that is not a lane of it, and what its padded lanes
    point at, equal to the bit, and moves its own lane's window one row
    on: the older row is what the newer was."""
    a = _runner(params, prefill_chunk_size=8)
    table = [3, 7]
    a.prefill_chunk(tokens[:8].tolist(), 0, table, 0.0)  # slot -1: no-op
    assert all((v == 0).all() for v in _state(a).values())
    a.collect(a.launch_chunk(tokens[:8].tolist(), 0, table, 0.0, slot=1))
    a.collect(a.launch_chunk(tokens[8:13].tolist(), 8, table, 0.0, slot=1))
    a.collect(a.launch_prefill(tokens[:9].tolist(), [5, 6], 0.0, slot=3))
    before = _state(a)
    # three lanes decode in a 4-lane bucket: slot 1 moves, the others do not
    a.decode([DecodeItem(int(tokens[13]), 13, table, 0.0, slot=1),
              DecodeItem(5, 0, [0], 0.0), DecodeItem(6, 0, [0], 0.0)])
    after = _state(a)
    np.testing.assert_array_equal(after["conv0"][:, 1], before["conv1"][:, 1])
    assert (after["conv1"][:, 1] != before["conv1"][:, 1]).any()
    for name in before:
        for idle in (0, 2, 3):
            np.testing.assert_array_equal(after[name][:, idle],
                                          before[name][:, idle])


def test_a_reused_slot_starts_from_zero(params, tokens, want):
    r = _runner(params)
    r.collect(r.launch_prefill(tokens[20:36].tolist(), [4, 5], 0.0, slot=0))
    assert np.abs(_state(r)["conv1"][:, 0]).max() > 0
    # the slot's next owner: whole prompt, then a chunked one
    _, last = r.collect(r.launch_prefill(tokens[:16].tolist(), [3, 7], 0.0,
                                         slot=0))
    assert _worst(last, want[15]) < TOL
    r.collect(r.launch_chunk(tokens[:16].tolist(), 0, [3, 7, 2], 0.0,
                             slot=0))
    _, last = r.collect(r.launch_chunk(tokens[16:21].tolist(), 16,
                                       [3, 7, 2], 0.0, slot=0))
    assert _worst(last, want[20]) < TOL


def _prompts(lengths, seed=0, vocab=60):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=n).tolist() for n in lengths]


def _run(engine, requests):
    streams = [engine.add_request(p, sp) for p, sp in requests]
    turns = 0
    while any(s.final() is None for s in streams):
        engine.step()
        turns += 1
        assert turns < 6000
    while engine.step():
        pass
    return [s.final() for s in streams]


def test_a_second_sequence_in_a_used_slot_equals_a_fresh_engine():
    """One lane, two requests one after the other: the second runs in the
    slot the first left its window in, and streams what a fresh engine
    streams for it."""
    first, second = _prompts((19, 13))
    sp = SamplingParams(max_tokens=6, logprobs=True)
    used = _engine(max_batch_size=1)
    used.generate(first, sp, drive=True)
    got = used.generate(second, sp, drive=True)
    fresh = _engine(max_batch_size=1).generate(second, sp, drive=True)
    assert got["token_ids"] == fresh["token_ids"]
    assert got["logprobs"] == fresh["logprobs"]
    assert used.stats()["state"]["resets"] == 2


def test_a_preempted_sequence_recomputes_to_the_same_continuation():
    """A pool too small for three long answers preempts; recompute runs
    the victim's prompt + generated tokens from position 0 into a zeroed
    slot, so its continuation is the uninterrupted one."""
    reqs = [(p, SamplingParams(max_tokens=14, logprobs=True))
            for p in _prompts((9, 11, 7))]
    roomy = _run(_engine(), reqs)
    tight_engine = _engine(num_blocks=13)
    tight = _run(tight_engine, reqs)
    assert sum(f["preemptions"] for f in tight) > 0
    st = tight_engine.stats()["state"]
    assert st["resets"] == 3 + sum(f["preemptions"] for f in tight)
    for a, b in zip(roomy, tight):
        assert a["token_ids"] == b["token_ids"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=TOL)


def test_a_repeated_prompt_takes_no_prefix_match_and_the_counter_says_so():
    e = _engine(enable_prefix_cache=True)
    prompt = _prompts((17,))[0]
    sp = SamplingParams(max_tokens=5, logprobs=True)
    first = e.generate(prompt, sp, drive=True)
    again = e.generate(prompt, sp, drive=True)
    assert again["token_ids"] == first["token_ids"]
    assert again["logprobs"] == first["logprobs"]
    assert again["cached_tokens"] == 0
    st = e.stats()
    assert st["prefix_hit_pages"] == 0 and st["blocks_cached"] == 0
    # asked for and declined: each reset is a match not attempted
    assert st["state"]["prefix_declined"] is True
    assert st["state"]["resets"] == 2
    assert _engine(enable_prefix_cache=False).stats()["state"][
        "prefix_declined"] is False
    assert st["state"]["slots"] == 4 and st["state"]["bytes"] \
        == 4 * 5 * 2 * 64 * 4  # slots x layers x rows x hidden x float32


def test_the_state_account_counts_carried_programs_and_owned_lanes():
    """17 tokens in chunks of 8: one fresh program and two carried; 5
    tokens, of which decode steps make 4, one lane each; on the metrics
    page too."""
    from ray_tpu.util.metrics import prometheus_text
    from ray_tpu.util.watchtower import parse_prometheus

    def page(name):
        return sum(n for (metric, tags), n in
                   parse_prometheus(prometheus_text()).items()
                   if metric == name and dict(tags).get("model") == "lfm2")

    was = {n: page(n) for n in ("serve_llm_state_carried_total",
                                "serve_llm_state_decode_lanes_total")}
    e = _engine()
    e.generate(_prompts((17,))[0], SamplingParams(max_tokens=5), drive=True)
    st = e.stats()["state"]
    assert (st["resets"], st["carried"]) == (1, 2)
    assert st["decode_steps"] == {"1": 4} and st["decode_lanes"] == 4
    assert page("serve_llm_state_carried_total") \
        - was["serve_llm_state_carried_total"] == 2
    assert page("serve_llm_state_decode_lanes_total") \
        - was["serve_llm_state_decode_lanes_total"] == 4
    # two lanes at once: steps of two rows, two slots owned each
    e = _engine()
    _run(e, [(p, SamplingParams(max_tokens=4)) for p in _prompts((6, 6))])
    st = e.stats()["state"]
    assert st["decode_lanes"] > sum(st["decode_steps"].values())
    assert "2" in st["decode_steps"]


def test_speculation_is_refused_for_a_stateful_family():
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def test_the_routing_account_counts_pairs_on_held_experts():
    e = _engine()
    e.generate(_prompts((10,))[0], SamplingParams(max_tokens=4), drive=True)
    moe_stats = e.stats()["moe"]
    for kind in ("prefill", "decode"):
        acc = moe_stats[kind]
        assert len(acc["expert_pairs"]) == CFG.num_experts
        lo, n = CFG.expert_offset, CFG.experts_held
        assert acc["held_pairs"] == sum(acc["expert_pairs"][lo:lo + n])
        assert 0 < acc["held_pairs"] < acc["pairs"]


def test_sixty_four_lanes_decode_in_one_program():
    """The first configuration over 32 lanes: 70 requests on 64 lanes,
    every one finished, decode programs of 64 rows among them, every slot
    reset once a request and every page given back."""
    e = _engine(max_batch_size=64, num_blocks=64 * 12 + 8, block_size=4,
                max_model_len=40)
    reqs = [(p, SamplingParams(max_tokens=6))
            for p in _prompts([5 + i % 9 for i in range(70)], seed=3)]
    finals = _run(e, reqs)
    assert all(f["finish_reason"] == "length" and len(f["token_ids"]) == 6
               for f in finals)
    st = e.stats()
    assert st["state"]["slots"] == 64 and st["state"]["resets"] == 70
    assert st["state"]["decode_steps"].get("64", 0) > 0
    assert st["running"] == 0 and st["blocks_used"] == 0


# --------------------------------------------------------------------------
# one request through serve.run()


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


@pytest.mark.parametrize("lanes", [1, 4])
def test_requests_through_serve_run(llm_cluster, lanes):
    """`serve.run(build_llm_app(model="lfm2", preset="tiny"))` with one
    lane and with several: as many requests as lanes stream at once, each
    the tokens the engine gives it alone, and the replica drains."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app
    from ray_tpu.util import state

    conf = {"block_size": 8, "num_blocks": 64, "max_model_len": 64,
            "max_batch_size": lanes, "prefill_chunk_size": 16}
    handle = serve.run(build_llm_app(model="lfm2", preset="tiny",
                                     engine_config=conf), name="llm")
    try:
        prompts = _prompts([20 + 3 * i for i in range(lanes)], seed=5)
        sh = handle.options(stream=True)
        gens = [sh.remote({"prompt": p, "max_tokens": 5}) for p in prompts]
        finals = [None] * lanes

        def consume(i, gen):
            finals[i] = [ray_tpu.get(r, timeout=120) for r in gen][-1]

        threads = [threading.Thread(target=consume, args=(i, g))
                   for i, g in enumerate(gens)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        alone = LLMEngine(EngineConfig(model="lfm2", preset="tiny", **conf))
        for p, final in zip(prompts, finals):
            assert final["done"] and final["finish_reason"] == "length"
            assert final["token_ids"] == alone.generate(
                p, SamplingParams(max_tokens=5), drive=True)["token_ids"]
        (stats,) = state.llm_status("llm")
        assert stats["running"] == 0 and stats["blocks_used"] == 0
        assert stats["state"]["resets"] == lanes
    finally:
        serve.delete("llm")


# --------------------------------------------------------------------------
# layer parity (benchmark/parity_lfm2.py): what decides `correct` in the
# benchmark's cell beside the log-prob tolerance. In float32 a sound
# program reads 1e-6; the limits here stand where the cell's stand to its
# bf16 readings, a few times a sound reading.

PARITY_LIMITS = {"op_conv": 1e-4, "op_attention": 1e-4, "ffn_dense": 1e-4,
                 "ffn_experts": 1e-4, "window_conv": 1e-4, "routing": 0.02}


def _parity_config(limits=PARITY_LIMITS):
    return {**{k: list(v) if isinstance(v, tuple) else v
               for k, v in ARCH.items()},
            "model": {"config": "ray_tpu.models.lfm2:Lfm2Config.tiny"},
            "engine": {"model_config": {}, "prefill_chunk_size": 32},
            "layer_parity": {"rows": 75, "limits": limits}}


@pytest.mark.parametrize("fault, program, low, over", [
    ("sound", {}, {}, set()),
    ("the program takes its experts for 3-6", {"expert_offset": 3}, {},
     {"ffn_experts"}),
    ("the program lets a token choose 1", {"num_experts_per_tok": 1}, {},
     {"ffn_experts", "routing"}),
    ("the program does not renormalise", {"norm_topk_prob": False}, {},
     {"ffn_experts"}),
    ("the program selects without the bias", {"use_expert_bias": False}, {},
     {"ffn_experts", "routing"}),
    ("the program rotates by another base", {"rope_theta": 1e4}, {},
     {"op_attention"}),
    ("a chunk started from zeros", {}, {"drop_window_at": 32},
     {"op_conv"}),
    ("float8 operands", {}, {"operand_dtype": jnp.float8_e4m3fn},
     {"op_conv", "op_attention", "ffn_dense", "ffn_experts",
      "window_conv"}),
    ("the routed experts left out", {}, {"reference_params": "no we_down"},
     {"ffn_experts"}),
])
def test_layer_parity_tells_a_fault_from_rounding(params, tokens, fault,
                                                  program, low, over):
    from benchmark import parity_lfm2 as parity

    cfg = dataclasses.replace(CFG, **program)
    if "reference_params" in low:  # the reference's side without them
        low = {"reference_params": {**params, "layers": [
            {**p, "we_down": jnp.zeros_like(p["we_down"])}
            if "we_down" in p else p for p in params["layers"]]}}
    readings = parity.layer_parity(params, tokens[:75], cfg, ARCH, 32, **low)
    got = {k for k, limit in PARITY_LIMITS.items() if readings[k] > limit}
    assert got == over, (fault, readings)
    if not over:  # float32 against float32: an order of operations apart
        assert max(readings.values()) < 2e-5, readings


def _edge_engine():
    return _engine(max_model_len=80, prefill_chunk_size=32, num_blocks=128)


def _every_chunk_starts_fresh(engine):
    real = engine.runner._forward
    engine.runner._forward = lambda fn, state, slots, *a, fresh=None, **kw: \
        real(fn, state, slots, *a,
             fresh=None if fresh is None else True, **kw)


def _a_later_chunk_runs_in_the_next_lanes_slot(engine):
    real = engine.runner.launch_chunk

    def launch(token_ids, start, table, temperature, top_k, top_p, slot):
        lanes = engine.runner.max_batch_size
        return real(token_ids, start, table, temperature, top_k, top_p,
                    (slot + 1) % lanes if start else slot)
    engine.runner.launch_chunk = launch


def _decode_leaves_the_window_where_it_was(engine, monkeypatch):
    from ray_tpu.serve.llm import cache

    monkeypatch.setattr(cache.StateView, "set_all",
                        lambda self, index, name, rows: None)


BOTH = {"edge_logprob", "edge_window"}


@pytest.mark.parametrize("fault, break_engine, low, over", [
    ("sound", None, {}, set()),
    ("the reference drops the window at the edge", None,
     {"drop_window_at": 32}, BOTH),
    ("every chunk starts its slot from zero", _every_chunk_starts_fresh, {},
     BOTH),
    ("a later chunk runs in the next lane's slot",
     _a_later_chunk_runs_in_the_next_lanes_slot, {}, BOTH),
    # its one decode step reads the window before it would have moved it
    ("a decode step leaves the window where it was",
     _decode_leaves_the_window_where_it_was, {}, {"edge_window"}),
])
def test_the_engines_leg_sees_what_happens_at_a_chunks_edge(
        tokens, monkeypatch, fault, break_engine, low, over):
    """`parity_lfm2.serve_edge` / `edge_parity`: the engine itself on
    three prompts at once that end just past a chunk's edge, its
    log-probs and the rows left in its slots against the reference's."""
    from benchmark import parity_lfm2 as parity

    engine = _edge_engine()
    if break_engine is _decode_leaves_the_window_where_it_was:
        break_engine(engine, monkeypatch)
    elif break_engine:
        break_engine(engine)
    served, slots = parity.serve_edge(engine, tokens, 32, drive=True)
    assert [len(c["prompt"]) for c in served] == [33, 34, 65]
    assert slots.shape == (5, 4, 2, 64)
    readings = parity.edge_parity(engine.runner.params, served, slots, ARCH,
                                  **low)
    assert {k for k, v in readings.items() if v > 1e-3} == over, \
        (fault, readings)
    if not over:
        assert max(readings.values()) < 2e-5, readings
        state = engine.stats()["state"]
        assert (state["resets"], state["carried"]) == (3, 4)


def test_a_layer_out_of_parity_fails_the_cells_comparison(
        tokens, tmp_path, monkeypatch, capsys):
    """`parity_lfm2.serve_reference`, what the configuration names: the
    plain reference's log-probs where every half-layer and the engine
    that serves the weights are within their limits, and out of any
    tolerance where one is not."""
    from benchmark import parity_lfm2 as parity

    engine = _edge_engine()
    params = engine.runner.params
    stop = threading.Event()

    def loop():  # as the deployment's
        while not stop.is_set():
            if not engine.step():
                time.sleep(0.001)

    stepping = threading.Thread(target=loop, daemon=True)
    stepping.start()
    cases = [{"prompt": tokens[:70].tolist(),
              "tokens": tokens[70:75].tolist()},
             {"prompt": tokens[:9].tolist(), "tokens": tokens[9:12].tolist()}]
    limits = {**PARITY_LIMITS, "edge_logprob": 1e-4, "edge_window": 1e-4}
    path = tmp_path / "config.json"
    monkeypatch.setattr(ref, "_CONFIG", str(path))
    try:
        path.write_text(json.dumps(_parity_config(limits)))
        plain = ref.serve_reference(params, None, cases)
        assert parity.serve_reference(params, None, cases) == plain
        assert "within limits" in capsys.readouterr().out
        for name in ("window_conv", "edge_window"):
            path.write_text(json.dumps(_parity_config(
                {**limits, name: -1.0})))
            failed = parity.serve_reference(params, None, cases)
            assert f"FAILED: {name}" in capsys.readouterr().out
            assert all(abs(a - b - parity.FAILED) < 1e-3
                       for x, y in zip(plain, failed) for a, b in zip(x, y))
    finally:
        stop.set()
        stepping.join()


def test_a_dict_of_fields_is_laid_over_the_preset():
    """How a configuration file gives the seeded distribution: the
    published preset keeps its `initializer_range`."""
    assert Lfm2Config.lfm2_8b_a1b().initializer_range == 0.02
    engine = _engine(model_config={"initializer_range": 0.05})
    assert engine.model_cfg == dataclasses.replace(
        CFG, initializer_range=0.05)
    router = np.asarray(engine.runner.params["layers"][2]["router"])
    assert 0.04 < router.std() < 0.06
