"""The ling3 family (`Ling3Config.tiny`: layers ``kda kda mla kda``, a
dense feed-forward then routed experts under a group limit, 4 of 16
experts held) against the plain reference the benchmark compares with on
the chip (`benchmark/reference_ling3.py`: the KDA recurrence token by
token, latent attention up-projected), on seeded random weights, and what
the family asks of the serve engine: a recurrent state a lane slot BESIDE
pages of the latent kind.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (the chunked form against the
token scan, the absorbed read of cached latent rows against up-projected
heads, carried state against one full pass), which moves a logit of
magnitude up to 4 by 5e-6 here; 3e-5 leaves room for a platform's
reduction order, and every mutation measured moves the logits past it by
a factor of ten and more (`test_each_mechanism_shows`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_ling3 as ref
from benchmark.selftest import tiny_ling3
from ray_tpu.models.ling3 import Ling3Config, init_ling3
from ray_tpu.serve.llm.cache import StateLayout
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 3e-5
CFG = Ling3Config.tiny()
ARCH = ref.arch_of(CFG)


def _seeded(cfg, seed=7):
    p = init_ling3(jax.random.PRNGKey(seed), cfg)
    # norm scales away from 1, so that one left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("mixer_norm", "ffn_norm", "o_norm", "kv_norm"):
            if name in layer:
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    next(keys), layer[name].shape)
    p["lnf"] = 1.0 + 0.2 * jax.random.normal(next(keys), p["lnf"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (120,), 1, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens[:96]), ARCH)[0])


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, cfg=CFG, **kw):
    args = dict(block_size=8, num_blocks=40, max_model_len=128,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["ling3"], cfg, params, **args)


def _engine(**overrides):
    kw = dict(model="ling3", preset="tiny", block_size=4, num_blocks=96,
              max_model_len=48, max_batch_size=4, prefill_chunk_size=8,
              seed=0)
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _state(runner):
    return jax.tree.map(np.asarray, runner.state)


def _prompts(lengths, seed=0, vocab=60):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=n).tolist() for n in lengths]


def _run(engine, requests):
    streams = [engine.add_request(p, sp) for p, sp in requests]
    turns = 0
    while any(s.final() is None for s in streams):
        engine.step()
        turns += 1
        assert turns < 3000
    while engine.step():
        pass
    return [s.final() for s in streams]


def test_the_adapter_says_what_the_family_caches():
    ad = adapters()["ling3"]
    (kind,) = ad.kv_kinds(CFG)
    assert (kind.layers, kind.latent, kind.select, kind.v_head_dim) \
        == (1, True, None, 0) and CFG.kinds == ("kda", "kda", "mla", "kda")
    assert ad.state_fn(CFG)[0] == 3
    cut = Ling3Config.flash_l7_ep32()
    (kind,) = ad.kv_kinds(cut)
    assert (kind.layers, kind.n_kv_head, kind.head_dim) == (1, 1, 640)
    assert [(n, s, jnp.dtype(d)) for n, s, d in cut.state_parts()] == [
        ("conv0", (12288,), jnp.dtype(jnp.bfloat16)),
        ("conv1", (12288,), jnp.dtype(jnp.bfloat16)),
        ("conv2", (12288,), jnp.dtype(jnp.bfloat16)),
        ("s", (32, 128, 128), jnp.dtype(jnp.float32))]
    layers, parts = ad.state_fn(cut)
    layout = StateLayout(layers, 64, parts)
    assert layout.slot_bytes == 6 * (2_097_152 + 73_728)  # 13.0 MB a lane
    assert ad.held_experts(cut) == (0, 16)


def test_the_published_preset_and_its_cut():
    full = Ling3Config.flash()
    assert (full.n_layer, full.n_kda_layers, full.n_kv_layers) == (42, 35, 7)
    assert [i for i, k in enumerate(full.kinds) if k == "mla"] \
        == [5, 11, 17, 23, 29, 35, 41]
    cut = Ling3Config.flash_l7_ep32()
    assert cut.kinds == ("kda",) * 6 + ("mla",)
    shapes = jax.eval_shape(lambda: init_ling3(jax.random.PRNGKey(0), cut))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    pad = 2 * (cut.padded_vocab - cut.vocab_size) * cut.hidden_size
    assert n - pad == cut.n_params() == 1_105_070_016
    assert {a.dtype for a in jax.tree.leaves(shapes)} \
        == {jnp.dtype(jnp.bfloat16)}
    same = {f.name for f in dataclasses.fields(cut)} - {
        "num_hidden_layers", "first_k_dense_replace", "layer_types",
        "experts_held", "vocab_size", "max_position_embeddings"}
    assert all(getattr(cut, f) == getattr(full, f) for f in same)
    tiny = jax.eval_shape(lambda: init_ling3(jax.random.PRNGKey(0), CFG))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tiny)) \
        == CFG.n_params()


def test_whole_prompt_prefill_matches_the_reference(params, tokens, want):
    for n in (16, 13):  # a full bucket, and one with padded rows
        r = _runner(params)
        _, last = r.prefill(tokens[:n].tolist(), [3, 7], 0.0)
        assert _worst(last, want[n - 1]) < TOL


def test_the_reference_in_row_blocks_is_the_reference(params, tokens, want,
                                                      monkeypatch):
    """At the cell's 8,448 parity rows the reference's attention and
    feed-forwards run 512 rows at a time (`lax.map`, the last block
    padded): the same logits, at blocks of 40 rows over 96."""
    monkeypatch.setattr(ref, "ROW_BLOCK", 40)
    ref._layer.clear_cache()
    try:
        blocked = ref.forward(params, jnp.asarray(tokens[:96]), ARCH)[0]
    finally:
        ref._layer.clear_cache()
    assert _worst(blocked, want) < TOL


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_prefill_then_decode_match_the_reference(params, tokens,
                                                         want, chunk):
    """A prompt of 77 tokens in chunks (8 and 16: one block of the chunked
    form a program; 64: a block of four sub-blocks, the last program
    padded), S and the conv window carried in the lane's slot and the
    latent rows in pages under a permuted table, then four decode steps
    through both."""
    r = _runner(params, prefill_chunk_size=chunk)
    table = [3, 7, 2, 9, 5, 11, 13, 17, 19, 21, 23]
    n, at = 77, 0
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


@pytest.mark.parametrize("lanes", [1, 3, 4])
def test_lanes_under_permuted_tables_and_slots_decode_together(
        params, tokens, want, lanes):
    """1, 3 and as many lanes as there are slots in one decode program,
    each at its own position of the same sequence, in slots that are not
    their lane numbers and pages that are in no order: every lane's
    logits the reference's, and every slot no lane owns equal to the bit
    afterwards."""
    r = _runner(params)
    slots = [3, 0, 2, 1][:lanes]
    lengths = [9, 14, 21, 11][:lanes]
    tables = [[2 * i + 9, 2 * i + 2, 2 * i + 1] for i in range(4)]
    for slot, n, table in zip(slots, lengths, tables):
        r.collect(r.launch_prefill(tokens[:n].tolist(), table, 0.0,
                                   slot=slot))
    before = _state(r)
    _, logits = r.decode([
        DecodeItem(int(tokens[n]), n, table, 0.0, slot=slot)
        for slot, n, table in zip(slots, lengths, tables)])
    for i, n in enumerate(lengths):
        assert _worst(logits[i], want[n]) < TOL
    after = _state(r)
    for name in before:
        for slot in range(4):
            moved = (after[name][:, slot] != before[name][:, slot]).any()
            assert moved == (slot in slots), (name, slot)


def test_engine_logprobs_match_the_reference(params, tokens):
    """Prefill (chunked), then decode through the engine, overlapped loop
    and all: the streamed log-probs against the reference's one full
    forward over prompt + streamed tokens."""
    e = LLMEngine(EngineConfig(
        model="ling3", preset="tiny", block_size=8, num_blocks=24,
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
    st = e.stats()
    assert st["state"]["kernel_steps"] == 0  # the jnp step serves
    assert st["kv"]["latent"]["latent"] is True


MUTATIONS = {name: {"arch": {**ARCH, "leave_out": (name,)}} for name in (
    "safe_gate", "beta", "l2", "conv", "group_limit")}
MUTATIONS["state_bfloat16"] = {"state_dtype": jnp.bfloat16}
MUTATIONS["operands_bfloat16"] = {"operand_dtype": jnp.bfloat16}
MUTATIONS["held_one_on"] = {"arch": {**ARCH, "expert_offset": 5}}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_each_mechanism_shows(params, tokens, want, mutation):
    """The controls of TOL: the reference with a mechanism left out, the
    other reading of the gate, the state or the operands in bfloat16, lies
    outside it by a factor of ten and more."""
    kw = dict(MUTATIONS[mutation])
    wrong = ref.forward(params, jnp.asarray(tokens[:96]),
                        kw.pop("arch", ARCH), **kw)[0]
    assert _worst(wrong, want) > 10 * TOL


def test_every_share_of_the_experts_sums_to_the_uncut_layer():
    """The share test: the parts the four shares of 4 experts give, the
    shared expert counted once, add up to what the reference gives for
    the layer with all 16 experts held."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     _seeded(whole)["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(2), (40, CFG.hidden_size))
    arch = ref.arch_of(whole)
    want, chosen = ref.feed_forward(h, p, True, arch)
    from ray_tpu.models import mla

    total = 0.0
    for share in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=4 * share)
        held = {k: (v[4 * share:4 * share + 4] if k.startswith("we_") else v)
                for k, v in p.items()}
        y, counts = jax.jit(mla.experts, static_argnums=2)(h, held, cfg)
        shared = mla.swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"],
                            jnp.float32)
        total = total + y - (shared if share else 0.0)
        assert np.array_equal(np.asarray(counts), np.bincount(
            np.asarray(chosen).ravel(), minlength=16))
    assert _worst(total, want) < TOL


def test_a_preempted_sequence_recomputes_to_the_same_continuation():
    reqs = [(p, SamplingParams(max_tokens=10, logprobs=True))
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


def test_prefix_reuse_is_declined_and_speculation_refused():
    e = _engine(enable_prefix_cache=True)
    prompt = _prompts((17,))[0]
    sp = SamplingParams(max_tokens=5, logprobs=True)
    first = e.generate(prompt, sp, drive=True)
    again = e.generate(prompt, sp, drive=True)
    assert again["token_ids"] == first["token_ids"]
    assert again["cached_tokens"] == 0
    st = e.stats()
    assert st["prefix_hit_pages"] == 0 and st["blocks_cached"] == 0
    assert st["state"]["prefix_declined"] is True
    assert st["state"]["resets"] == 2
    assert st["state"]["carried"] == 2 * 2  # 17 tokens: chunks of 8, 8, 1
    assert st["state"]["slots"] == 4 and st["state"]["layers"] == 3
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def _cases(tokens):
    return [{"prompt": tokens[:24].tolist(), "tokens": tokens[24:32].tolist()}]


def test_layer_parity_reads_rounding_on_a_sound_program(params, tokens):
    found = tiny_ling3.readings(params, _cases(tokens))
    assert set(found) == set(tiny_ling3.parity.READINGS)
    assert max(found.values()) < tiny_ling3.LIMIT


@pytest.mark.parametrize("control,leg,kw", [
    ("safe_gate", "kda_gate", {}), ("beta", "kda_gate", {}),
    ("l2", "kda_state", {}), ("conv", "decode_mixer", {}),
    ("group_limit", "routing", {}),
    (None, "kda_state", {"state_dtype": jnp.bfloat16}),
    (None, "ffn_experts", {"arch": {**ARCH, "expert_offset": 5}})])
def test_layer_parity_sees_each_control(params, tokens, control, leg, kw):
    if control:
        kw = {"arch": {**ARCH, "leave_out": (control,)}}
    found = tiny_ling3.readings(params, _cases(tokens), **kw)
    assert found[leg] > 10 * tiny_ling3.LIMIT


def test_layer_parity_fails_a_program_whose_state_is_not_a_number(params,
                                                                  tokens):
    """A not-a-number on the PROGRAM's side (a chunked state that
    overflowed) reads infinite, over any limit, and not 0."""
    first = dict(params["layers"][0])
    k_column = CFG.kda.conv_dim // 3  # in_proj's columns: q~ | k~ | v~ | ...
    first["in_proj"] = first["in_proj"].at[0, k_column].set(jnp.nan)
    broken = {**params, "layers": [first, *params["layers"][1:]]}
    found = tiny_ling3.readings(broken, _cases(tokens),
                                reference_params=params)
    assert found["kda_state"] == found["mixer"] == float("inf")
    assert not found["kda_state"] <= tiny_ling3.LIMIT
