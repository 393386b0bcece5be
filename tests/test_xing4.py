"""The xing4 family (`Xing4Config.tiny`: four residual streams of 64 mixed
every half-layer by a Sinkhorn-projected matrix, latent attention that
reads every cached slot under YaRN frequencies, one leading dense
feed-forward, then 16 sigmoid-routed SwiGLU experts of which 4 are held
beside a shared one) against the plain reference the benchmark compares
with on the chip (`benchmark/reference_xing4.py`), the reference's
attention and routed layer against `transformers`' DeepseekV3 classes, on
seeded random weights, and what its latent kind WITHOUT an indexer asks of
the serve engine: one pool a layer, every lane read to its own length.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (absorbed against up-projected,
tiles under a running softmax against one full score matrix, the streams
side by side in one row against an (n, C) matrix), which moves a logit of
magnitude 0.1-0.7 by under 1e-6 here; 2e-5 leaves room for a platform's
reduction order, and every mutation of `test_each_mechanism_shows` moves
the logits past it by an order of magnitude or more. BF16_TOL: in
bfloat16 the states X are rounded after each of the six half-layers'
mixes and every product's operands are 8 bits wide; the cached path and
the whole-prompt path round at other places (absorbed against
up-projected), so two sound bfloat16 programs differ by a few bfloat16
steps of a logit of magnitude 1: 0.08 here against a float32 reference,
where a mechanism left out moves a logit by 0.3 or more."""

import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing4 as ref
from benchmark.selftest import tiny_xing4
from ray_tpu.models import mla
from ray_tpu.models import xing4 as xg
from ray_tpu.models.xing4 import Xing4Config, init_xing4
from ray_tpu.ops import mhc_maps
from ray_tpu.serve.llm import cache
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-5
BF16_TOL = 0.08
CFG = Xing4Config.tiny()
T = 80


ARCH = tiny_xing4.arch()  # the reference's keys off `Xing4Config.tiny()`


def _seeded(cfg, seed=7):
    p = init_xing4(jax.random.PRNGKey(seed), cfg)
    # norm scales and the maps' alphas away from 1, so that one left out
    # shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("attn_norm", "ffn_norm", "q_norm", "kv_norm"):
            layer[name] = (1.0 + 0.2 * jax.random.normal(
                next(keys), layer[name].shape)).astype(layer[name].dtype)
        for half in ("hc_attn", "hc_ffn"):
            layer[half]["alpha"] = 1.0 + 0.3 * jax.random.normal(
                next(keys), (3,))
    p["lnf"] = (1.0 + 0.2 * jax.random.normal(
        next(keys), p["lnf"].shape)).astype(p["lnf"].dtype)
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
    """Tiles of 2 pages (8 slots), so that a context of 80 slots is ten
    tiles and the groups of a decode step reach different ones."""
    monkeypatch.setattr(cache, "DENSE_LATENT_TILE_SLOTS", 8)


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def _runner(params, cfg=CFG, **kw):
    args = dict(block_size=4, num_blocks=64, max_model_len=96,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["xing4"], cfg, params, **args)


def _engine(**overrides):
    kw = dict(model="xing4", preset="tiny", block_size=4, num_blocks=96,
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


# ------------------------------------- the reference against transformers


def _hf_config(**kw):
    from transformers import DeepseekV3Config

    return DeepseekV3Config(
        vocab_size=CFG.vocab_size, hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        moe_intermediate_size=CFG.moe_intermediate_size,
        num_hidden_layers=2, num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_attention_heads,
        n_shared_experts=1, n_routed_experts=CFG.n_routed_experts,
        routed_scaling_factor=CFG.routed_scaling_factor,
        kv_lora_rank=CFG.kv_lora_rank, q_lora_rank=CFG.q_lora_rank,
        qk_rope_head_dim=CFG.qk_rope_head_dim, v_head_dim=CFG.v_head_dim,
        qk_nope_head_dim=CFG.qk_nope_head_dim, n_group=1, topk_group=1,
        num_experts_per_tok=CFG.num_experts_per_tok, norm_topk_prob=True,
        max_position_embeddings=CFG.max_position_embeddings,
        rms_norm_eps=CFG.rms_norm_eps, rope_theta=CFG.rope_theta,
        rope_scaling=dict(ARCH["rope_scaling"]), rope_interleave=True,
        attention_bias=False, attn_implementation="eager", **kw)


def _t(a):
    import torch

    return torch.tensor(np.asarray(a, np.float32))


def test_reference_attention_is_transformers_deepseek_v3s(params):
    """The reference's MLA with YaRN (blended frequencies, mscale^2 on the
    scores, interleaved pairs) against `DeepseekV3Attention` on the same
    seeded weights, 48 rows: three times the original context of 16, so
    the ramp and the scaled pairs all turn."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf

    config = _hf_config()
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["layers"][0])
    attn = hf.DeepseekV3Attention(config, 0).eval()
    H = CFG.num_attention_heads
    wkv_b = jnp.concatenate([p["wk_b"], p["wv_b"]], axis=-1)  # (R, H, n+v)
    with torch.no_grad():
        attn.q_a_proj.weight.copy_(_t(p["wq_a"].T))
        attn.q_a_layernorm.weight.copy_(_t(p["q_norm"]))
        attn.q_b_proj.weight.copy_(_t(p["wq_b"].T))
        attn.kv_a_proj_with_mqa.weight.copy_(_t(p["wkv_a"].T))
        attn.kv_a_layernorm.weight.copy_(_t(p["kv_norm"]))
        attn.kv_b_proj.weight.copy_(_t(wkv_b.reshape(CFG.kv_lora_rank,
                                                      -1).T))
        attn.o_proj.weight.copy_(_t(p["wo"].T))
    n = 48
    h = jax.random.normal(jax.random.PRNGKey(3), (n, CFG.hidden_size))
    rotary = hf.DeepseekV3RotaryEmbedding(config)
    np.testing.assert_allclose(  # YaRN's frequencies, before any product
        rotary.inv_freq.numpy(), ref.frequencies(ARCH), rtol=1e-6)
    assert ref.score_scale(ARCH) == pytest.approx(attn.scaling, rel=1e-6)
    assert ref.score_scale(ARCH) > 1.4 / np.sqrt(CFG.qk_head_dim)
    cos_sin = rotary(_t(h)[None], torch.arange(n)[None])
    mask = torch.full((n, n), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        theirs = attn(_t(h)[None], cos_sin, mask)[0][0].numpy()
    with jax.default_matmul_precision("highest"):
        ours = ref.attention_half(h, p, ARCH)
    assert np.abs(theirs).max() > 1e-3
    assert _worst(ours, theirs) < 1e-5 * max(1.0, np.abs(theirs).max())
    # and the program's frequencies are the reference's
    np.testing.assert_allclose(
        np.asarray(mla.yarn_frequencies(
            CFG.rope_theta, CFG.qk_rope_head_dim, factor=CFG.rope_factor,
            original=CFG.original_max_position_embeddings,
            beta_fast=CFG.rope_beta_fast, beta_slow=CFG.rope_beta_slow)),
        ref.frequencies(ARCH), rtol=1e-6)


def test_reference_routed_layer_is_transformers_deepseek_v3s():
    """Sigmoid scores, the selection bias, 3 of 16, weights normalised and
    times the scale, the shared expert: `DeepseekV3MoE` with all 16
    experts held."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf

    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     _seeded(whole)["layers"][1])
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    moe = hf.DeepseekV3MoE(_hf_config()).eval()
    with torch.no_grad():
        moe.gate.weight.copy_(_t(p["router"].T))
        moe.gate.e_score_correction_bias.copy_(_t(p["router_bias"]))
        for e, expert in enumerate(moe.experts):
            expert.gate_proj.weight.copy_(_t(p["we_gate"][e].T))
            expert.up_proj.weight.copy_(_t(p["we_up"][e].T))
            expert.down_proj.weight.copy_(_t(p["we_down"][e].T))
        moe.shared_experts.gate_proj.weight.copy_(_t(p["ws_gate"].T))
        moe.shared_experts.up_proj.weight.copy_(_t(p["ws_up"].T))
        moe.shared_experts.down_proj.weight.copy_(_t(p["ws_down"].T))
    h = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size))
    with torch.no_grad():
        theirs = moe(_t(h)[None])[0].numpy()
    with jax.default_matmul_precision("highest"):
        ours, _ = ref.ffn_half(h, p, True, {**ARCH, "expert_offset": 0})
        program, _ = mla.experts(h, p, whole)
    assert _worst(ours, theirs) < 1e-5
    assert _worst(program, theirs) < 1e-5


# ------------------------------------------------ the residual streams


def _logits_that_spread(n_tokens=33, n=4, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, n, n_tokens))


@pytest.mark.parametrize("which", ["program", "reference"])
def test_twenty_sinkhorn_iterations_make_h_res_doubly_stochastic(which):
    """Logits of std 1, as the seeded maps give (they spread over some
    five units): after 20 iterations every row and column sums to 1
    within 1e-4; after 2 the columns do not."""
    M = jnp.exp(_logits_that_spread())

    def run(iters):
        if which == "program":
            return np.asarray(mhc_maps.sinkhorn(M, iters, CFG.hc_eps))
        return np.moveaxis(np.asarray(ref.sinkhorn(
            jnp.moveaxis(M, -1, 0), iters, CFG.hc_eps)), 0, -1)

    done = run(20)
    assert np.abs(done.sum(0) - 1).max() < 1e-4
    assert np.abs(done.sum(1) - 1).max() < 1e-4
    assert np.abs(done - 0.25).max() > 0.4  # far from uniform
    assert np.abs(run(2).sum(0) - 1).max() > 1e-2
    if which == "program":  # the two are the same numbers
        np.testing.assert_allclose(done, np.moveaxis(np.asarray(ref.sinkhorn(
            jnp.moveaxis(M, -1, 0), 20, CFG.hc_eps)), 0, -1), atol=1e-6)


@pytest.mark.parametrize("tokens_in", [19, 700])
def test_the_maps_kernel_is_the_jnp_form(tokens_in):
    """`mhc_maps` in interpret mode (one block of 19 tokens; two of 512,
    the second padded) against `maps_reference`, all 20 iterations."""
    K, n = CFG.hc_maps, CFG.hc_mult
    u = 1.5 * jax.random.normal(jax.random.PRNGKey(1), (K, tokens_in))
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (K, 1))
    bias = jax.random.normal(jax.random.PRNGKey(3), (K, 1))
    how = dict(n=n, iters=20, eps=CFG.hc_eps, lo=-30.0, hi=30.0)
    got = mhc_maps.mhc_maps(u, scale, bias, interpret=True, **how)
    want = mhc_maps.maps_reference(u * scale + bias, **how)
    assert got.shape == want.shape == (K, tokens_in)
    assert _worst(got, want) < 1e-6
    assert float(want[n:2 * n].max()) > 1.0 > float(want[:n].max())


def test_the_maps_and_mixes_are_the_references(params):
    """A half-layer's coefficients and both mixes, program (one row of n C
    lanes, tokens last in the coefficients) against reference (an (n, C)
    matrix a token), on states whose streams differ."""
    m = params["layers"][1]["hc_ffn"]
    n, C = CFG.hc_mult, CFG.hidden_size
    X = jax.random.normal(jax.random.PRNGKey(5), (19, n, C))
    y = jax.random.normal(jax.random.PRNGKey(6), (19, C))
    pre, post, res = xg.mhc_coefficients(X.reshape(19, -1), m, CFG)
    with jax.default_matmul_precision("highest"):
        rpre, rpost, rres = ref.mhc_maps(X, m, ARCH)
    assert _worst(pre.T, rpre) < 1e-5 and _worst(post.T, rpost) < 1e-5
    assert _worst(jnp.moveaxis(res, -1, 0), rres) < 1e-5
    assert np.abs(np.asarray(rres) - np.eye(n)).max() > 0.3
    assert np.abs(np.asarray(rres) - 0.25).max() > 0.3
    assert np.asarray(rpost).max() > 1.0  # the factor 2
    assert _worst(xg.mhc_pre(X.reshape(19, -1), pre, CFG),
                  ref.mix_pre(X, rpre)) < 1e-5
    assert _worst(xg.mhc_post(X.reshape(19, -1), y, post, res, CFG),
                  ref.mix_post(X, y, rpost, rres).reshape(19, -1)) < 1e-5


# ---------------------------------------------------- against the reference


def test_whole_forward_matches_the_reference(params, tokens, want):
    got = xg.xing4_prefill_kv(params, jnp.asarray(tokens)[None], CFG)[0][0]
    assert np.abs(want).max() > 0.1
    assert _worst(got, want) < TOL


def test_the_reference_in_row_blocks_is_the_reference(params, tokens, want,
                                                      monkeypatch):
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    ref._layer.clear_cache()
    got = ref.forward(params, jnp.asarray(tokens), ARCH)[0]
    ref._layer.clear_cache()
    assert _worst(got, want) < 1e-6


def test_chunks_then_decode_through_a_permuted_table(params, tokens, want,
                                                     small_tiles):
    """Chunks of 16 rows through the one pool under a block table that is
    a seeded permutation of the pages, each attending every cached slot
    and its own rows, then decode steps: every program's last row against
    the reference's full forward."""
    r = _runner(params)
    assert r.layouts[0].tile_pages == 2
    assert r.k_pages[0].shape[-1] == CFG.latent_row
    assert r.v_pages[0].size == 0  # one pool a layer
    table = (1 + np.random.default_rng(1).permutation(40))[:-(-T // 4)] \
        .tolist()
    n = 64
    for s in range(0, n, 16):
        _, logits = r.prefill_chunk(tokens[s:s + 16].tolist(), s, table, 0.0)
        assert _worst(logits, want[s + 15]) < TOL, s
    for pos in range(n, T):
        _, logits = r.decode([DecodeItem(int(tokens[pos]), pos, table, 0.0)])
        assert _worst(logits[0], want[pos]) < TOL, pos
    by = r.context_slots
    assert by["prefill"]["rows"] == n
    assert by["prefill"]["row_slots"] == 16 * (0 + 16 + 32 + 48)
    assert by["decode"]["rows"] == T - n
    assert by["decode"]["row_slots"] == sum(range(n, T))


def test_decode_lanes_of_unlike_lengths_in_groups(params, small_tiles):
    """Eight lanes at lengths from 1 to 75 in one decode program (groups
    of 2 rows, longest first), among them a lane of one slot, of exactly
    one tile (8) and of a tile and one slot (9): each lane's logits are
    its own sequence's reference."""
    r = _runner(params, max_batch_size=8, num_blocks=256)
    rng = np.random.default_rng(3)
    lengths = [75, 1, 40, 8, 64, 9, 33, 52]
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
    assert by["slots_valid"] == by["row_slots"] == sum(lengths)
    assert by["rows"] == 8 and by["slots_read"] >= by["slots_valid"]
    assert "slots_selected" not in by  # nothing is chosen


def test_absorbed_path_equals_the_up_projected_path(params, tokens):
    """A prompt's own rows up-projected (the prompt program) against the
    same rows absorbed on their latents (a chunk from position 0 with
    nothing cached)."""
    r = _runner(params, prefill_chunk_size=32, max_model_len=64)
    table = list(range(1, 9))
    _, up = r.prefill(tokens[:32].tolist(), table, 0.0)
    _, absorbed = r.prefill_chunk(tokens[:32].tolist(), 0, table, 0.0)
    assert _worst(up, absorbed) < TOL


def test_bfloat16_programs_stay_near_the_reference(tokens, small_tiles):
    """The same tree in bfloat16 through both paths (BF16_TOL's reason is
    in the module's docstring); the float32 reference on the bfloat16
    weights."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    p = init_xing4(jax.random.PRNGKey(7), cfg)
    want = np.asarray(ref.forward(p, jnp.asarray(tokens), ARCH)[0])
    got = xg.xing4_prefill_kv(p, jnp.asarray(tokens)[None], cfg)[0][0]
    assert _worst(got, want) < BF16_TOL
    r = _runner(p, cfg)
    table = list(range(1, 1 + -(-T // 4)))
    for s in range(0, 64, 16):
        _, logits = r.prefill_chunk(tokens[s:s + 16].tolist(), s, table, 0.0)
        assert _worst(logits, want[s + 15]) < BF16_TOL, s
    for pos in range(64, 72):
        _, logits = r.decode([DecodeItem(int(tokens[pos]), pos, table, 0.0)])
        assert _worst(logits[0], want[pos]) < BF16_TOL, pos
    # the maps stay float32 in a bfloat16 tree
    assert p["layers"][0]["hc_attn"]["phi"].dtype == jnp.float32
    assert p["layers"][0]["wq_a"].dtype == jnp.bfloat16


@pytest.mark.parametrize("mutation", [
    "two_sinkhorn_iterations", "h_post_factor_1", "no_dynamic_maps",
    "plain_frequencies", "no_mscale", "no_shared_expert", "no_routed_scale",
    "bfloat16_coefficients"])
def test_each_mechanism_shows(params, tokens, want, mutation):
    """The reference with one mechanism changed differs from the
    reference by far more than TOL: the comparison above would catch the
    program doing the same."""
    arch = {
        "two_sinkhorn_iterations": {**ARCH, "hc_sinkhorn_iters": 2},
        "h_post_factor_1": {**ARCH, "h_post_factor": 1.0},
        "no_dynamic_maps": {**ARCH, "hc_dynamic": False},
        "plain_frequencies": {**ARCH, "rope_scaling": None},
        "no_mscale": {**ARCH, "mscale_squared": False},
        "no_shared_expert": {**ARCH, "n_shared_experts": 0},
        "no_routed_scale": {**ARCH, "routed_scaling_factor": 1.0},
        "bfloat16_coefficients": {**ARCH, "coef_dtype": "bfloat16"},
    }[mutation]
    got = ref.forward(params, jnp.asarray(tokens), arch)[0]
    assert _worst(got, want) > 10 * TOL


# ------------------------------------------------------------ the shares


def test_every_share_of_the_experts_sums_to_the_uncut_layer():
    """The four shares of 4 experts each, the shared expert counted once,
    add up to the layer with all 16 held: program and reference."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = _seeded(whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size))
    full, _ = mla.experts(h, p, whole)
    shared = mla.swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"],
                        jnp.float32)
    parts, theirs = 0, 0
    for offset in range(0, 16, 4):
        cut = dataclasses.replace(CFG, expert_offset=offset)
        held = {**p, **{n: p[n][offset:offset + 4]
                        for n in ("we_gate", "we_up", "we_down")}}
        parts = parts + mla.experts(h, held, cut)[0] - shared
        theirs = theirs + ref.ffn_half(
            h, held, True, {**ARCH, "expert_offset": offset})[0] - shared
    assert _worst(parts + shared, full) < TOL
    assert _worst(theirs + shared, full) < TOL
    assert float(jnp.abs(shared).max()) > 100 * TOL


def test_four_vocabulary_slices_give_the_whole_logits(params, tokens, want):
    """The head's columns in four slices of 128 (the embedding whole, as
    ids index it): each slice's logits are the whole's columns."""
    for at in range(0, CFG.vocab_size, 128):
        part = {**params, "lm_head": params["lm_head"][:, at:at + 128]}
        got = xg.xing4_prefill_kv(part, jnp.asarray(tokens[:24])[None],
                                  CFG)[0][0]
        assert _worst(got, want[:24, at:at + 128]) < TOL


def test_the_parameters_are_the_issues_count():
    """Layer 0 (dense) and five expert layers with 16 of 64 experts and a
    quarter of the vocabulary: 1,445.6 M parameters, and the published
    model's 29,505,505,264 (ISSUE 51's 29,505,163,760 from the matrices
    and maps plus 341,504 of norm scales), as many as a seeded tree holds
    but for the head's and the embedding's padding."""
    cfg = Xing4Config.xing4_29b_a4b_l6_ep4()
    assert cfg.n_params() == 1_445_615_236
    assert Xing4Config.xing4_29b_a4b().n_params() == 29_505_505_264
    for c in (cfg, CFG):
        tree = jax.eval_shape(lambda k: init_xing4(k, c),
                              jax.random.PRNGKey(0))
        held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
        padding = 2 * (c.padded_vocab - c.vocab_size) * c.hidden_size
        assert held == c.n_params() + padding
    with open(ref._CONFIG) as f:
        config = json.load(f)
    assert config["parameters"] == cfg.n_params()
    published = {**config["published"],
                 **{"rope_" + k: v for k, v in
                    config["published"]["rope_scaling"].items()}}
    for key, value in published.items():
        if key not in config["reduced_keys"] and hasattr(cfg, key):
            assert getattr(cfg, key) == value, key
    assert cfg.original_max_position_embeddings == \
        config["published"]["rope_scaling"][
            "original_max_position_embeddings"]


def test_importing_the_family_builds_nothing():
    for module in (xg, mla):
        assert not [n for n, v in vars(module).items()
                    if isinstance(v, jax.Array)]


# --------------------------------------------- glm_dsa's programs, as before

# sha256 of the StableHLO text of glm_dsa's three serve programs at its tiny
# preset, recorded at the parent commit (ece6e4a, before latent attention's
# parts moved to models/mla.py) by `_lowered_glm` itself, and anew at PR 56,
# whose programs take a launch's host arguments as one array
# (tests/test_mimo_v2.py says what holds their bodies)
GLM_DSA_AT_THE_PARENT = {
    "prefill":
        "022ea93e39491a151d4e1cae07b26ecd98b269be63a4a349bb84f387e8bc844f",
    "chunk":
        "7b20899cbfeca403cc933e0426f02de1d0ec4633099405e2a0908c6cbb2e732c",
    "decode":
        "45a192bef6367ab553faf1ed0980d8f1e1ff340d74bc0e3e90d0ccaa446fc762",
}


def _lowered_glm(_cache={}):
    if _cache:
        return _cache
    adapter = adapters()["glm_dsa"]
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
        name: hashlib.sha256(re.sub(
            r' \{jax\.result_info = "[^"]*"\}', "",
            low.as_text()).encode()).hexdigest()
        for name, low in texts.items()})
    return _cache


@pytest.mark.parametrize("which", sorted(GLM_DSA_AT_THE_PARENT))
def test_glm_dsas_programs_lower_as_before(which):
    """glm_dsa's prefill, chunk and decode programs lower to the StableHLO
    they lowered to before its latent attention moved to models/mla.py
    and its fold of the latent tiles got a sibling without a choice."""
    assert _lowered_glm()[which] == GLM_DSA_AT_THE_PARENT[which]


# ------------------------------------------------------ through the engine


def test_the_adapter_describes_a_latent_kind_without_an_indexer():
    (kind,) = adapters()["xing4"].kv_kinds(CFG)
    # 24 latent lanes, 8 rotated, 8 of padding; at Xing4.0 512 + 64 + 64
    assert kind == cache.KVKind("latent", 3, 1, 40, 0, None, None)
    assert kind.latent and kind.select is None
    lay = cache.KVLayout.of(kind, 8, 4)
    assert lay.shape == (3, 8, 4, 40) and lay.v_shape == (3, 8, 4, 0)
    assert lay.token_bytes(2) == {"latent": 240, "index": 0}
    assert lay.block_bytes(2) == 3 * 4 * 40 * 2
    big = cache.KVLayout.of(cache.KVKind(
        *Xing4Config.xing4_29b_a4b_l6_ep4().kv_kinds()[0]), 8, 16)
    assert big.token_bytes(2) == {"latent": 7680, "index": 0}
    assert big.tile_pages == 64  # 1,024 slots
    # a full kind's V is as wide as its K where nothing is said
    full = cache.KVLayout(2, 8, 4, 2, 16)
    assert full.v_row == full.row == 32 and not full.latent


def test_served_logprobs_are_the_references(params, small_tiles):
    """Requests of 20 to 70 tokens through the engine (chunks, lanes in
    one decode program, the pool shared): the log-probs of the eight
    tokens each streamed are the reference's, and the new counters
    move."""
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
    kv = stats["kv"]["latent"]
    assert kv["latent"] and kv["select"] is None
    assert kv["token_bytes"] == {"latent": 480, "index": 0}
    assert kv["pages_used"] == 0
    decode = stats["context"]["decode"]
    # 4 lanes x 7 steps (the first token is the prompt program's)
    assert decode["rows"] == 28
    assert decode["row_slots"] == sum(
        n + i for n in (70, 20, 45, 33) for i in range(7))
    assert stats["context"]["prefill"]["row_slots"] > 0
    assert stats["moe"]  # the routed layers' pairs are reported


def test_decode_steps_read_with_the_kernel_as_on_a_tpu(params, read_by_kernel,
                                                       monkeypatch):
    """The engine on the path a TPU takes (conftest `read_by_kernel`: the
    Pallas kernel, interpreted, two pages a step): the same requests'
    log-probs are the reference's still, every decode step's launch is
    counted in `kernel_steps` and what it read in `slots_read` to each
    lane's own page, and a chunk keeps its loops."""
    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "STEP_BYTES", 1)
    monkeypatch.setattr(pa, "STEP_SLOTS_MIN", 8)
    monkeypatch.setattr(pa, "STARTS_A_TURN", 2)
    read_by_kernel(True)
    engine = _engine()
    engine.update_weights(1, params)
    rng = np.random.default_rng(5)
    lengths = (70, 20, 45, 33)
    prompts = [rng.integers(1, CFG.vocab_size, n).tolist() for n in lengths]
    finals = _serve(engine, prompts, [8] * 4, logprobs=True)
    cases = [{"prompt": p, "tokens": f["token_ids"]}
             for p, f in zip(prompts, finals)]
    wants = ref.serve_reference(params, None, cases, arch=ARCH)
    for f, w in zip(finals, wants):
        np.testing.assert_allclose(f["logprobs"], w, atol=2e-4)
    stats = engine.stats()
    by = stats["context_by_kind"]["latent"]
    assert by["decode"]["kernel_steps"] == stats["steps"]["decode"] > 0
    assert by["prefill"]["kernel_steps"] == 0
    # 4 lanes x 7 steps, each lane read to the page (of 4 slots) it ends in
    at = [n + i for n in lengths for i in range(7)]
    assert by["decode"]["slots_read"] == sum(-(-n // 4) * 4 for n in at)
    assert by["decode"]["slots_valid"] == by["decode"]["row_slots"] == sum(at)
    assert by["decode"]["rows"] == 28
    read_by_kernel(False)
    loops = _engine()
    loops.update_weights(1, params)
    again = _serve(loops, prompts, [8] * 4)
    assert [f["token_ids"] for f in again] == [f["token_ids"] for f in finals]
    by = loops.stats()["context_by_kind"]["latent"]["decode"]
    assert by["kernel_steps"] == 0 and by["slots_read"] > sum(at)


def test_a_prefix_is_taken_on_the_latent_kind(params):
    """A latent page depends on the prefix alone (every stream starts as
    the token, the maps see only earlier rows through attention): the
    second request takes the first's pages and streams the same tokens."""
    engine = _engine()
    engine.update_weights(1, params)
    prompt = np.random.default_rng(6).integers(1, CFG.vocab_size, 50).tolist()
    first = _serve(engine, [prompt], [6])[0]
    second = _serve(engine, [prompt], [6])[0]
    assert first["token_ids"] == second["token_ids"]
    kv = engine.stats()["kv"]["latent"]
    assert kv["prefix_taken"] == 1 and kv["prefix_declined"] == 0


def test_a_preempted_lane_is_resumed(params):
    """Preempted by hand mid-decode: the recompute (its whole history as
    a new prompt) continues with the tokens of the uninterrupted run."""
    prompt = np.random.default_rng(8).integers(1, CFG.vocab_size, 19).tolist()
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
              and dict(tags).get("model") == "xing4" and n > 0}
    assert {"rows", "row_slots", "slots_valid"} <= series


# ---- the benchmark's layer parity (benchmark/parity_xing4.py), tiny


def _parity(params, tokens, arch=ARCH, **kw):
    from benchmark import parity_xing4 as parity
    return parity.layer_parity(params, tokens.tolist(), CFG, arch, chunk=16,
                               page=4, decode_rows=40, **kw)


def test_layer_parity_reads_rounding_on_a_sound_program(params, tokens,
                                                        small_tiles):
    """Chunks of 16 through a permuted table, then the last 40 rows as
    decode steps of eight lanes at contexts of 40 to 79 slots: float32 on
    both sides, so every leg reads rounding."""
    sound = _parity(params, tokens)
    assert sound["routing"] == 0.0
    assert max(sound.values()) < 1e-5, sound


@pytest.mark.parametrize("control,leg", [
    ({"hc_sinkhorn_iters": 2}, "mhc_coef"),
    ({"h_post_factor": 1.0}, "mhc_coef"),
    ({"hc_dynamic": False}, "mhc_coef"),
    ({"coef_dtype": "bfloat16"}, "mhc_coef"),
    ({"rope_scaling": None, "score_scale": ref.score_scale(ARCH)},
     "decode_mixer"),
    ({"mscale_squared": False}, "mixer"),
    ({"routed_scaling_factor": 1.0}, "ffn_experts")])
def test_layer_parity_sees_each_control(params, tokens, small_tiles, control,
                                        leg):
    """The reference made wrong in one way moves its leg by three orders
    of magnitude over a sound reading, and leaves the legs it has no part
    in where they were."""
    got = _parity(params, tokens, {**ARCH, **control})
    assert got[leg] > 1e-3, got
    others = {"mhc_coef": ("mixer", "ffn_experts"),
              "mixer": ("mhc_coef", "ffn_experts"),
              "decode_mixer": ("mhc_coef", "ffn_experts"),
              "ffn_experts": ("mhc_coef", "mixer")}[leg]
    assert all(got[k] < 1e-5 for k in others), got


@pytest.mark.parametrize("fault", ["another_lanes_table", "one_slot_off",
                                   "one_lane_a_row_short"])
def test_layer_parity_sees_a_fault_in_the_decode_steps_read(
        params, tokens, small_tiles, monkeypatch, fault):
    """A fault that only a decode step's read has, in one lane of eight,
    moves the decode rows' own leg, which reads the WORST row (at the
    cell's size the decoded rows are 64 of 8,448, which the 90th
    percentile over all rows passes)."""
    from ray_tpu.ops import context_attention as ca
    real = ca.attend_latent

    def faulty(q, latent, own_valid, ctx, *args, **kw):
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
        return real(q, latent, own_valid, ctx, *args, **kw)

    # (the read is `mla.attend_cached`'s since PR 61: ling3 runs it too)
    monkeypatch.setattr(mla, "attend_latent", faulty)
    from benchmark import parity_xing4 as parity
    parity._program_rows.clear_cache()
    try:
        got = _parity(params, tokens)
    finally:
        parity._program_rows.clear_cache()
    assert got["decode_mixer"] > 1e-2, got
