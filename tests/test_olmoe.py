"""OLMoE through the llama path (`LlamaConfig.olmoe_tiny`: routed SwiGLU
experts with none dropped, q/k norm, an untied head) against the plain
reference the benchmark compares with on the chip
(`benchmark/reference_olmoe.py`), on seeded random weights.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (grouped rows against all experts
for every token, a scan against a loop, cached context against a full
pass), which moves a logit of magnitude 0.2-1 by 3e-7 here; 2e-5 leaves
room for a platform's reduction order, and every mutation below moves the
logits past three times it: one expert fewer a token 2.3e-2, renormalised
weights 8.1e-2, no q/k norm 3.8e-1, and the gentlest lower precision, the
expert weights alone rounded to bfloat16, 1.1e-4 (ISSUE 27 proposed 1e-4,
which that last one would pass)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_olmoe as ref
from ray_tpu.models.llama import (LlamaConfig, init_llama, llama_forward,
                                  llama_prefill_kv)
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-5
CFG = LlamaConfig.olmoe_tiny()
ARCH = {"num_attention_heads": CFG.n_head,
        "num_experts_per_tok": CFG.n_experts_per_tok,
        "norm_topk_prob": CFG.norm_topk_prob, "rms_norm_eps": CFG.rms_eps,
        "rope_theta": CFG.rope_theta, "vocab_size": CFG.vocab_size}


@pytest.fixture(scope="module")
def params():
    p = init_llama(jax.random.PRNGKey(7), CFG)
    # norm scales away from 1, so that a norm left out or misplaced shows
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    for i, name in enumerate(("ln_attn", "ln_mlp", "q_norm", "k_norm")):
        p["blocks"][name] = 1.0 + 0.2 * jax.random.normal(
            k[i], p["blocks"][name].shape)
    p["lnf"] = 1.0 + 0.2 * jax.random.normal(k[4], p["lnf"].shape)
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (40,), 1, CFG.vocab_size), np.int32)


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def test_full_forward_matches_the_reference(params, tokens):
    want, _ = ref.forward(params, jnp.asarray(tokens), ARCH)
    got = llama_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert got.shape == want.shape == (40, CFG.padded_vocab)
    assert _worst(got, want) < TOL


@pytest.mark.parametrize("paged", [False, True, "page-tiles"],
                         ids=["dense", "paged", "dense-page-tiles"])
def test_prefill_chunk_and_decode_match_the_reference(params, tokens, paged,
                                                      context_tile_pages,
                                                      read_by_kernel):
    """A 16-token prefill, a second chunk that crosses into a new page and
    ends mid-page, then three decode steps through the paged cache: each
    step's logits against the reference's one full pass. The dense
    programs read the context as these toy rows make it (one tile holds
    the table) and in tiles of one page (the chunk reads two, the decode
    steps four of a table's eight); `paged`: the decode steps read it
    with the Pallas kernel, as on a TPU."""
    if paged == "page-tiles":
        context_tile_pages(1)
        paged = False
    read_by_kernel(paged)
    want, _ = ref.forward(params, jnp.asarray(tokens), ARCH)
    want = np.asarray(want)
    r = ModelRunner(adapters()["llama"], CFG, params, block_size=8,
                    num_blocks=16, max_model_len=64, max_batch_size=2,
                    prefill_chunk_size=16)
    table = [3, 7, 2, 9, 5]
    _, last = r.prefill(tokens[:16].tolist(), table, 0.0)
    assert _worst(last, want[15]) < TOL
    _, last = r.prefill_chunk(tokens[16:29].tolist(), 16, table, 0.0)
    assert _worst(last, want[28]) < TOL
    for pos in (29, 30, 31):
        _, logits = r.decode([DecodeItem(int(tokens[pos]), pos, table, 0.0)])
        assert _worst(logits[0], want[pos]) < TOL
    assert len(r.take_expert_pairs()) == 5  # one (L, E) array a program
    by = r.context_by_kind["full"]
    assert by["decode"]["kernel_steps"] == (3 if paged else 0)
    assert by["prefill"]["kernel_steps"] == 0  # a chunk keeps its loop


def test_no_pair_is_dropped_under_skew(params):
    """One token repeated: every position routes alike, so the favoured
    experts receive several times the 1.25 k N / E pairs at which the old
    capacity layer began to drop. Still equal to the reference."""
    toks = jnp.full((32,), 11, jnp.int32)
    logits, _, _, counts = llama_prefill_kv(params, toks[None], CFG)
    cap = 1.25 * CFG.n_experts_per_tok * 32 / CFG.n_experts
    assert int(counts.max()) > 2 * cap
    want, _ = ref.forward(params, toks, ARCH)
    assert _worst(logits[0], want) < TOL


def _system(params, tokens, **changes):
    cfg = dataclasses.replace(CFG, **changes)
    return llama_forward(params, jnp.asarray(tokens)[None], cfg)[0]


@pytest.mark.parametrize("mutation", ["one_expert_fewer", "renormalised",
                                      "no_qk_norm", "bf16_experts"])
def test_the_tolerance_is_tight(params, tokens, mutation):
    """Each of these is a different model or a lower precision; the
    comparison that passes above must fail for it."""
    want, _ = ref.forward(params, jnp.asarray(tokens), ARCH)
    if mutation == "one_expert_fewer":
        got = _system(params, tokens,
                      n_experts_per_tok=CFG.n_experts_per_tok - 1)
    elif mutation == "renormalised":
        got = _system(params, tokens, norm_topk_prob=True)
    elif mutation == "no_qk_norm":
        got = _system(params, tokens, qk_norm=False)
    else:
        rounded = dict(params, blocks={
            k: v.astype(jnp.bfloat16).astype(v.dtype)
            if k.startswith("we_") else v
            for k, v in params["blocks"].items()})
        got = _system(rounded, tokens)
    assert _worst(got, want) > 3 * TOL


def test_pair_counts_are_the_references_routing(params, tokens):
    """The counts a program returns are its routing, whole: per layer and
    expert the number of tokens whose reference top-k holds that expert,
    and k N pairs a layer."""
    _, _, _, counts = llama_prefill_kv(params, jnp.asarray(tokens)[None], CFG)
    _, chosen = ref.forward(params, jnp.asarray(tokens), ARCH)
    want = np.stack([np.bincount(np.asarray(c).ravel(),
                                 minlength=CFG.n_experts) for c in chosen])
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert counts.shape == (CFG.n_layer, CFG.n_experts)
    assert (np.asarray(counts).sum(axis=1)
            == CFG.n_experts_per_tok * len(tokens)).all()


def test_engine_serves_olmoe_dense_and_paged_alike(read_by_kernel):
    """`EngineConfig(model="llama", preset="olmoe_tiny")` end to end: the
    greedy streams of the engine whose decode steps read their context
    with the loops and of the one that reads it with the kernel are equal, the
    engine accounts its routing by step kind, and a weight swap is taken
    up (other streams after it, the first ones again after swapping
    back)."""
    prompts = [list(range(1, 6)), list(range(3, 15)), list(range(2, 32))]
    sp = SamplingParams(max_tokens=6, temperature=0.0)

    def build(paged):
        read_by_kernel(paged)
        return LLMEngine(EngineConfig(
            model="llama", preset="olmoe_tiny", block_size=8, num_blocks=64,
            max_model_len=64, max_batch_size=4, prefill_chunk_size=16,
            seed=0))

    def streams(eng):
        return [eng.generate(p, sp, drive=True)["token_ids"]
                for p in prompts]

    # a program takes its path when it is first traced: one engine's
    # streams while its predicate stands
    dense = build(False)
    first = streams(dense)
    paged = build(True)
    assert streams(paged) == first
    ran = paged.stats()["context_by_kind"]["full"]["decode"]["kernel_steps"]
    assert ran == paged.stats()["steps"]["decode"] > 0
    assert dense.stats()["context_by_kind"]["full"]["decode"][
        "kernel_steps"] == 0
    read_by_kernel(False)
    moe = dense.stats()["moe"]
    k, L, E = CFG.n_experts_per_tok, CFG.n_layer, CFG.n_experts
    for kind in ("prefill", "decode"):
        acc = moe[kind]
        assert acc["layer_calls"] == L * dense.stats()["steps"][kind]
        assert acc["pairs"] == sum(acc["expert_pairs"]) and len(
            acc["expert_pairs"]) == E
        assert acc["pairs"] % k == 0
        assert 0 < acc["experts_touched"] <= E * acc["layer_calls"]
    old = dense.runner.params
    dense.update_weights(1, init_llama(jax.random.PRNGKey(99),
                                       dense.model_cfg))
    assert streams(dense) != first
    dense.update_weights(2, old)
    assert streams(dense) == first
