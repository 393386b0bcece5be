"""The granite_hybrid family (`GraniteHybridConfig.tiny`: layers ``mamba
mamba attention mamba``, a routed half and a shared MLP in every one, 6 of
12 experts held, one group) against the plain reference the benchmark
compares with on the chip (`benchmark/reference_granite_hybrid.py`), on
seeded random weights; the reference against the published `transformers`
implementation; the shared Mamba-2 mixer at both families' sizes; and what
the family asks of the serve engine.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (the chunked form against the
token-by-token recurrence, cached context and carried state against one
full pass), which moves a logit of magnitude 0.05-0.44 (the logits are
divided by `logits_scaling` 16) by 9e-8 here; 2e-6 leaves room for a
platform's reduction order, and every mutation measured moves the logits
past it: the recurrent state held in bfloat16 8.7e-6
(`test_a_bfloat16_state_is_seen`), bfloat16 matrix-product operands
4.3e-4, each of the four multipliers set to 1, the conv bias left out,
the shared MLP left out, the held range one expert off, one expert fewer
a token (`test_each_mechanism_shows`)."""

import dataclasses
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite_hybrid as ref
from ray_tpu.models import mamba2, moe
from ray_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    init_granite_hybrid,
)
from ray_tpu.serve.llm.cache import StateLayout, StateView
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-6
CFG = GraniteHybridConfig.tiny()
ARCH = ref.arch_of(CFG)


def _seeded(cfg, seed=7):
    p = init_granite_hybrid(jax.random.PRNGKey(seed), cfg)
    # norm scales and the skip away from 1, so that one left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("input_norm", "post_norm", "gate_norm", "D"):
            if name in layer:
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    next(keys), layer[name].shape)
    p["norm"] = 1.0 + 0.2 * jax.random.normal(next(keys), p["norm"].shape)
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
    return np.asarray(ref.forward(params, jnp.asarray(tokens[:48]), ARCH)[0])


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, cfg=CFG, **kw):
    args = dict(block_size=8, num_blocks=24, max_model_len=64,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["granite_hybrid"], cfg, params, **args)


def _engine(**overrides):
    kw = dict(model="granite_hybrid", preset="tiny", block_size=4,
              num_blocks=96, max_model_len=48, max_batch_size=4,
              prefill_chunk_size=8, seed=0)
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
    ad = adapters()["granite_hybrid"]
    assert [k.layers for k in ad.kv_kinds(CFG)] == [1] and CFG.n_layer == 4
    layers, parts = ad.state_fn(CFG)
    assert layers == 3
    cut = GraniteHybridConfig.h_small_l10_ep4()
    # three conv rows in bf16, the SSM state float32 (`assumed`)
    assert [(n, s, jnp.dtype(d)) for n, s, d in cut.state_parts()] == [
        ("conv0", (8448,), jnp.dtype(jnp.bfloat16)),
        ("conv1", (8448,), jnp.dtype(jnp.bfloat16)),
        ("conv2", (8448,), jnp.dtype(jnp.bfloat16)),
        ("ssm", (128, 64, 128), jnp.dtype(jnp.float32))]
    layout = StateLayout(*ad.state_fn(cut)[:1], 64, cut.state_parts())
    assert layout.nbytes == 64 * 9 * (4_194_304 + 3 * 8448 * 2)
    assert ad.held_experts(cut) == (0, 18)
    conv = (8 * 16 + 2 * 16,)
    assert dict((n, s) for n, s, _ in parts) == {
        "conv0": conv, "conv1": conv, "conv2": conv, "ssm": (8, 16, 16)}


def test_the_published_preset_is_the_published_model():
    full = GraniteHybridConfig.h_small()
    assert (full.n_layer, full.n_ssm_layers, full.n_kv_layers) == (40, 36, 4)
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert full.attention_multiplier == 1.0 / full.head_dim
    cut = GraniteHybridConfig.h_small_l10_ep4()
    assert cut.layer_types == full.layer_types[:10]
    assert (cut.n_ssm_layers, cut.n_kv_layers) == (9, 1)
    shapes = jax.eval_shape(
        lambda: init_granite_hybrid(jax.random.PRNGKey(0), cut))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 2_955_758_208  # ISSUE 48's arithmetic
    assert {a.dtype for a in jax.tree.leaves(shapes)} \
        == {jnp.dtype(jnp.bfloat16)}
    whole = jax.eval_shape(
        lambda: init_granite_hybrid(jax.random.PRNGKey(0), full))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(whole)) \
        == 36 * 800_941_696 + 4 * 740_597_760 + 411_041_792 + 4096
    same = {f.name for f in dataclasses.fields(cut)} - {
        "layer_types", "experts_held", "vocab_size",
        "max_position_embeddings"}
    assert all(getattr(cut, f) == getattr(full, f) for f in same)


def test_whole_prompt_prefill_matches_the_reference(params, tokens, want):
    for n in (16, 13):  # a full bucket, and one with padded rows
        r = _runner(params)
        _, last = r.prefill(tokens[:n].tolist(), [3, 7], 0.0)
        assert _worst(last, want[n - 1]) < TOL


@pytest.mark.parametrize("chunk,tile_pages", [(8, None), (16, None),
                                              (32, None), (8, 1), (16, 2)])
def test_chunked_prefill_then_decode_match_the_reference(
        params, tokens, want, chunk, tile_pages, context_tile_pages):
    """The chunked form against the recurrence: a prompt of 37 tokens in
    chunks of `chunk` rows (8: one SSD chunk a program; 16 and 32: two and
    four, the last program padded), state carried in the lane's slot, then
    four decode steps; the attention layer reads the cached context whole
    and in tiles of one and two pages."""
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


@pytest.mark.parametrize("n", [31, 32, 33])
def test_a_prompt_that_ends_at_before_and_after_a_chunks_edge(
        params, tokens, want, n):
    """Chunks of 16: a prompt of 32 ends AT the second chunk's edge, 31
    one row before it (one padded row), 33 one row after (a last program
    of one real row, whose conv window and state are all carried)."""
    r = _runner(params)
    table = [3, 7, 2, 9, 5, 11]
    for at in range(0, n, 16):
        _, last = r.collect(r.launch_chunk(
            tokens[at:min(n, at + 16)].tolist(), at, table, 0.0, slot=1))
    assert _worst(last, want[n - 1]) < TOL
    _, logits = r.decode([DecodeItem(int(tokens[n]), n, table, 0.0, slot=1)])
    assert _worst(logits[0], want[n]) < TOL


def test_a_bfloat16_state_is_seen(params, tokens, want):
    """The control of TOL: the reference with its recurrent state rounded
    to bfloat16 after every token lies outside it."""
    low = ref.forward(params, jnp.asarray(tokens[:48]), ARCH,
                      state_dtype=jnp.bfloat16)[0]
    assert _worst(low, want) > 2 * TOL


@pytest.mark.parametrize("lanes", [1, 3, 4])
def test_lanes_decode_together_and_unowned_slots_are_written_back_as_read(
        params, tokens, want, lanes):
    """1, 3 and as many lanes as there are slots in one decode program,
    each at its own position of the same sequence, in slots that are not
    their lane numbers: every lane's logits the reference's, and every
    slot no lane owns equal to the bit afterwards."""
    r = _runner(params)
    slots = [3, 0, 2, 1][:lanes]
    lengths = [9, 14, 21, 11][:lanes]
    tables = [[2 * i + 1, 2 * i + 2, 2 * i + 9] for i in range(4)]
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
        model="granite_hybrid", preset="tiny", block_size=8, num_blocks=24,
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


MUTATIONS = {
    "embedding_multiplier 1": {"embedding_multiplier": 1.0},
    "residual_multiplier 1": {"residual_multiplier": 1.0},
    "attention_multiplier 1": {"attention_multiplier": 1.0},
    "logits_scaling 1": {"logits_scaling": 1.0},
    "all four multipliers 1": {
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
        "attention_multiplier": 1.0, "logits_scaling": 1.0},
    "the held range one expert on": {"expert_offset": 4},
    "one expert fewer a token": {"num_experts_per_tok": 2},
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_each_mechanism_shows(params, tokens, want, mutation):
    """Each multiplier is live, and the share is what the config says: the
    program with the key changed leaves the reference's logits (by far
    more than TOL) and lands on the reference's with the same change."""
    cfg = dataclasses.replace(CFG, **MUTATIONS[mutation])
    _, last = _runner(params, cfg).prefill(tokens[:16].tolist(), [3, 7], 0.0)
    assert _worst(last, want[15]) > 100 * TOL
    changed = ref.forward(params, jnp.asarray(tokens[:16]),
                          ref.arch_of(cfg))[0]
    scale = max(1.0, float(np.abs(np.asarray(changed[15])).max()))
    assert _worst(last, changed[15]) < TOL * scale


@pytest.mark.parametrize("left_out", ["conv_b", "ws_down"])
def test_the_conv_bias_and_the_shared_mlp_show(params, tokens, want,
                                               left_out):
    without = {**params, "layers": [
        {k: (jnp.zeros_like(v) if k == left_out else v)
         for k, v in p.items()} for p in params["layers"]]}
    _, last = _runner(without).prefill(tokens[:16].tolist(), [3, 7], 0.0)
    assert _worst(last, want[15]) > 100 * TOL


def test_the_tied_head_over_a_slice_of_the_vocabulary(params, tokens):
    """A chip's slice of the (tied) embedding: ids and logits over the
    first 256 rows alone are the whole model's first 256 logits, and the
    log-softmax over the slice is the slice's own."""
    ids = np.asarray(tokens[:16]) % 256
    cut = dataclasses.replace(CFG, vocab_size=256)
    sliced = {**params, "wte": params["wte"][:256]}
    _, whole = _runner(params).prefill(ids.tolist(), [3, 7], 0.0)
    _, part = _runner(sliced, cut).prefill(ids.tolist(), [3, 7], 0.0)
    assert part.shape == (256,) and whole.shape == (512,)
    assert _worst(part, whole[:256]) < TOL
    want = ref.forward(sliced, jnp.asarray(ids), ref.arch_of(cut))[0]
    assert _worst(part, want[15]) < TOL
    # the head IS the embedding: no second matrix in the tree
    assert set(params) == {"wte", "layers", "norm"}


def _wide(held=72, offset=0):
    """A small layer under the published router: 72 wide, 10 a token."""
    return dataclasses.replace(CFG, num_local_experts=72,
                               num_experts_per_tok=10, experts_held=held,
                               expert_offset=offset)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One layer's routed half: each of four chips holds 18 of the
    router's 72 experts (offsets 0, 18, 36, 54); their routed parts, with
    the shared MLP counted once, add up to what the uncut reference gives
    for the whole layer. The program is given each share in turn, its
    weights the slice a chip would hold."""
    from ray_tpu.models import granite_hybrid as gh

    whole = _wide()
    p = _seeded(whole)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        uncut, chosen = ref.feed_forward(h, p32, ref.arch_of(whole))
        shared, _ = ref.feed_forward(  # the routed sum zeroed out
            h, {**p32, "we_down": jnp.zeros_like(p32["we_down"])},
            ref.arch_of(whole))
    total, counts = 0.0, []
    for chip in range(4):
        lo = 18 * chip
        mine = {**p, **{k: p[k][lo:lo + 18]
                        for k in ("we_gate", "we_up", "we_down")}}
        y, c = gh._experts(h, mine, _wide(18, lo))
        total = total + y - (shared if chip else 0.0)
        counts.append(np.asarray(c))
    assert _worst(total, uncut) < TOL
    # the router's load is the model's, whatever is held
    assert all((c == counts[0]).all() for c in counts)
    assert counts[0].sum() == 24 * 10
    assert (counts[0] == np.bincount(np.asarray(chosen).ravel(),
                                     minlength=72)).all()


def test_softmax_over_the_chosen_logits_is_the_renormalised_softmax():
    """The published gate (the 10 largest LOGITS, softmax over them
    alone) is `route(score="softmax", norm_topk=True)`."""
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 72))
    w, e, counts, _ = moe.route(x, router, 10, True, score="softmax")
    logits = np.asarray(x @ router)
    top, chosen = jax.lax.top_k(logits, 10)
    assert (np.asarray(e) == np.asarray(chosen)).all()
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray(jax.nn.softmax(top, axis=-1)),
                               rtol=2e-6)
    assert int(counts.sum()) == 400


# --------------------------------------------------------------------------
# the shared Mamba-2 mixer (models/mamba2.py) at both families' sizes


def _mixer_weights(s, hidden, bias, key):
    ks = jax.random.split(key, 8)
    C, H = s.conv_dim, s.heads
    p = {"in_proj": jax.random.normal(ks[0], (hidden, s.d_inner + C + H))
         * 0.1,
         "conv_w": jax.random.uniform(ks[1], (s.conv_kernel, C), minval=-.5,
                                      maxval=.5),
         "dt_bias": jax.random.normal(ks[2], (H,)) - 2.0,
         "A_log": jnp.log(jax.random.uniform(ks[3], (H,), minval=1.0,
                                             maxval=16.0)),
         "D": 1.0 + 0.2 * jax.random.normal(ks[4], (H,)),
         "gate_norm": 1.0 + 0.2 * jax.random.normal(ks[5], (s.d_inner,)),
         "out_proj": jax.random.normal(ks[6], (s.d_inner, hidden)) * 0.1}
    if bias:
        p["conv_b"] = jax.random.uniform(ks[7], (C,), minval=-.5, maxval=.5)
    return p


@pytest.mark.parametrize("family", ["nemotron_h", "granite_hybrid"])
def test_the_shared_mixer_at_both_families_sizes(family):
    """One implementation, two callers: at nemotron_h's shape (8 groups,
    no conv bias given) and at Granite's (1 group, a conv bias), 19 rows
    as a fresh chunk of 16 (two chunks of the chunked form) and a carried
    one of 3 padded to 16, then one decode step among other slots, each
    against its own family's float32 reference."""
    from benchmark import reference_nemotron_h as ref_nh

    hidden, T = 32, 20
    if family == "nemotron_h":
        s = mamba2.Mamba2Sizes(heads=8, head_dim=8, state=16, groups=8,
                               conv_kernel=4, chunk=8, eps=1e-5,
                               dtype=jnp.float32)
        p = _mixer_weights(s, hidden, False, jax.random.PRNGKey(1))
        arch = {"mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 8,
                "ssm_state_size": 16, "conv_kernel": 4,
                "layer_norm_epsilon": 1e-5}

        def reference(u):  # nemotron_h's always adds its bias: zeros
            return ref_nh.mamba_mixer(
                u, {**p, "conv_b": jnp.zeros((s.conv_dim,))}, arch,
                jnp.matmul, jnp.float32)
    else:
        s = mamba2.Mamba2Sizes(heads=8, head_dim=8, state=16, groups=1,
                               conv_kernel=4, chunk=8, eps=1e-5,
                               dtype=jnp.float32)
        p = _mixer_weights(s, hidden, True, jax.random.PRNGKey(2))
        arch = {"mamba_n_heads": 8, "mamba_d_head": 8, "mamba_n_groups": 1,
                "mamba_d_state": 16, "mamba_d_conv": 4, "rms_norm_eps": 1e-5}

        def reference(u):
            return ref.mamba_mixer(u, p, arch, jnp.matmul, jnp.float32)[:2]

    u = jax.random.normal(jax.random.PRNGKey(5), (T, hidden))
    with jax.default_matmul_precision("highest"):
        want, want_state = reference(u)
    layout = StateLayout(1, 4, s.state_parts())
    buffers, out = layout.zeros(), []
    for at, end in ((0, 16), (16, T - 1)):
        rows = jnp.zeros((16, hidden)).at[:end - at].set(u[at:end])
        view = StateView(layout, buffers, jnp.int32(2), fresh=at == 0)
        out.append(mamba2.rows(rows, p, s, view, 0, end - at)[:end - at])
        buffers = view.buffers
    step = StateView(layout, buffers, jnp.asarray([-1, 2], jnp.int32))
    out.append(mamba2.step(jnp.stack([u[0], u[T - 1]]), p, s, step, 0)[1:])
    assert _worst(jnp.concatenate(out), want) < TOL
    assert _worst(step.buffers["ssm"][0, 2], want_state) < TOL
    for name, buf in step.buffers.items():  # the other slots: untouched
        assert (np.delete(np.asarray(buf), 2, axis=1) == 0).all(), name


def test_there_is_one_recurrence_under_ray_tpu():
    from ray_tpu.models import granite_hybrid, nemotron_h

    assert nemotron_h.ssd_chunked is mamba2.ssd_chunked
    assert not hasattr(granite_hybrid, "ssd_chunked")


# --------------------------------------------------------------------------
# the reference against the published implementation (no download: a tiny
# seeded model of the `transformers` class)


def _to_reference_tree(model, cfg):
    """A `GraniteMoeHybridForCausalLM`'s weights as the reference's tree:
    torch's (out, in) matrices transposed, the fused gate | up split."""
    def t(x):
        return jnp.asarray(x.detach().numpy())

    layers = []
    for kind, layer in zip(cfg.layer_types, model.model.layers):
        p = {"input_norm": t(layer.input_layernorm.weight),
             "post_norm": t(layer.post_attention_layernorm.weight)}
        if kind == "mamba":
            m = layer.mamba
            p.update(in_proj=t(m.in_proj.weight).T,
                     conv_w=t(m.conv1d.weight)[:, 0, :].T,
                     conv_b=t(m.conv1d.bias), dt_bias=t(m.dt_bias),
                     A_log=t(m.A_log), D=t(m.D), gate_norm=t(m.norm.weight),
                     out_proj=t(m.out_proj.weight).T)
        else:
            a = layer.self_attn
            p.update(wq=t(a.q_proj.weight).T, wk=t(a.k_proj.weight).T,
                     wv=t(a.v_proj.weight).T, wo=t(a.o_proj.weight).T)
        e = layer.block_sparse_moe
        F = cfg.intermediate_size
        fused = t(e.input_linear.weight)  # (E, 2F, D): gate rows, then up
        p.update(router=t(e.router.layer.weight).T,
                 we_gate=fused[:, :F].transpose(0, 2, 1),
                 we_up=fused[:, F:].transpose(0, 2, 1),
                 we_down=t(e.output_linear.weight).transpose(0, 2, 1))
        Fs = cfg.shared_intermediate_size
        shared = t(layer.shared_mlp.input_linear.weight)  # (2Fs, D)
        p.update(ws_gate=shared[:Fs].T, ws_up=shared[Fs:].T,
                 ws_down=t(layer.shared_mlp.output_linear.weight).T)
        layers.append(p)
    return {"wte": t(model.model.embed_tokens.weight), "layers": layers,
            "norm": t(model.model.norm.weight)}


def test_the_reference_computes_what_transformers_computes(tokens):
    """`transformers`' GraniteMoeHybridForCausalLM at the tiny sizes (every
    expert held: the published class has no share), random weights from a
    seed, `torch_forward`'s chunked form at chunks of 8 over 21 rows:
    its float32 logits are the reference's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    try:
        from transformers import (
            GraniteMoeHybridConfig,
            GraniteMoeHybridForCausalLM,
        )
    except ImportError:
        pytest.skip(f"transformers {transformers.__version__} has no "
                    "granitemoehybrid")
    cfg = dataclasses.replace(CFG, experts_held=12, expert_offset=0)
    torch.manual_seed(0)
    hf = GraniteMoeHybridConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.n_layer, layer_types=list(cfg.layer_types),
        intermediate_size=cfg.intermediate_size,
        shared_intermediate_size=cfg.shared_intermediate_size,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        num_local_experts=cfg.num_local_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state, mamba_n_groups=cfg.mamba_n_groups,
        mamba_d_conv=cfg.mamba_d_conv, mamba_expand=cfg.mamba_expand,
        mamba_chunk_size=cfg.mamba_chunk_size, mamba_conv_bias=True,
        mamba_proj_bias=False, embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, rms_norm_eps=cfg.rms_norm_eps,
        position_embedding_type="nope", tie_word_embeddings=True,
        hidden_act="silu", attention_bias=False, attention_dropout=0.0,
        initializer_range=0.1, max_position_embeddings=256,
        attn_implementation="eager")
    model = GraniteMoeHybridForCausalLM(hf).float().eval()
    with torch.no_grad():  # away from the init's constants
        for layer in model.model.layers:
            if layer.mamba is not None:
                layer.mamba.dt_bias.normal_(-2.0, 1.0)
                layer.mamba.D.normal_(1.0, 0.2)
                layer.mamba.norm.weight.normal_(1.0, 0.2)
            layer.input_layernorm.weight.normal_(1.0, 0.2)
            layer.post_attention_layernorm.weight.normal_(1.0, 0.2)
        model.model.norm.weight.normal_(1.0, 0.2)
        ids = torch.tensor(np.asarray(tokens[:21], np.int64))[None]
        theirs = model(input_ids=ids, use_cache=False).logits[0].numpy()
    tree = _to_reference_tree(model, cfg)
    ours = ref.forward(tree, jnp.asarray(tokens[:21]), ref.arch_of(cfg))[0]
    assert np.abs(theirs).max() > 0.05
    assert _worst(ours, theirs) < TOL


# --------------------------------------------------------------------------
# what the family asks of the serve engine


def test_padded_rows_leave_a_state_equal(params, tokens):
    """The same 13 tokens through chunks of 8 + 5 rows (the second padded
    to 8) and through one program of 16 rows (3 padded) leave the same
    conv window to the bit and the same SSM state to rounding, and a
    program without a slot (warm-up) writes nothing."""
    a, b = _runner(params, prefill_chunk_size=8), _runner(params)
    table = [3, 7]
    a.prefill_chunk(tokens[:8].tolist(), 0, table, 0.0)  # slot -1: no-op
    assert all((v == 0).all() for v in _state(a).values())
    a.collect(a.launch_chunk(tokens[:8].tolist(), 0, table, 0.0, slot=1))
    a.collect(a.launch_chunk(tokens[8:13].tolist(), 8, table, 0.0, slot=1))
    b.collect(b.launch_prefill(tokens[:13].tolist(), table, 0.0, slot=1))
    sa, sb = _state(a), _state(b)
    for name in ("conv0", "conv1", "conv2"):
        np.testing.assert_array_equal(sa[name][0], sb[name][0])
        np.testing.assert_allclose(sa[name], sb[name], atol=1e-5)
    np.testing.assert_allclose(sa["ssm"], sb["ssm"], atol=1e-5)
    assert np.abs(sa["ssm"][:, 1]).max() > 0
    for name in sa:  # other slots untouched
        assert (np.delete(sa[name], 1, axis=1) == 0).all()


def test_a_reused_slot_starts_from_zero(params, tokens, want):
    r = _runner(params)
    r.collect(r.launch_prefill(tokens[20:36].tolist(), [4, 5], 0.0, slot=0))
    assert np.abs(_state(r)["ssm"][:, 0]).max() > 0
    _, last = r.collect(r.launch_prefill(tokens[:16].tolist(), [3, 7], 0.0,
                                         slot=0))
    assert _worst(last, want[15]) < TOL


def test_a_preempted_sequence_recomputes_to_the_same_continuation():
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
    assert st["state"]["slots"] == 4 and st["state"]["bytes"] > 0
    assert st["state"]["carried"] == 2 * 2  # 17 tokens: chunks of 8, 8, 1
    assert _engine(enable_prefix_cache=False).stats()["state"][
        "prefix_declined"] is False


def test_speculation_is_refused_for_a_stateful_family():
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def test_the_accounts_report_for_this_family_with_no_change_of_shape():
    """`engine_stats()["moe"]` (pairs over ALL experts, the held ones',
    experts touched) and `["state"]` (fresh and carried prefill programs,
    decode steps by rows and slots owned), as for every family."""
    e = _engine()
    _run(e, [(p, SamplingParams(max_tokens=4)) for p in _prompts((10, 19))])
    stats = e.stats()
    for kind in ("prefill", "decode"):
        acc = stats["moe"][kind]
        assert set(acc) == {"pairs", "expert_pairs", "experts_touched",
                            "layer_calls", "held_pairs",
                            "held_experts_touched"}
        assert len(acc["expert_pairs"]) == CFG.num_local_experts
        lo, n = CFG.expert_offset, CFG.experts_held
        assert acc["held_pairs"] == sum(acc["expert_pairs"][lo:lo + n])
        assert 0 < acc["held_pairs"] < acc["pairs"]
        # a routed half in EVERY layer
        assert acc["layer_calls"] % CFG.n_layer == 0
    st = stats["state"]
    assert (st["resets"], st["carried"]) == (2, 1 + 2)
    assert sum(st["decode_steps"].values()) > 0
    assert st["decode_lanes"] == 2 * 3
    nemotron = LLMEngine(EngineConfig(
        model="nemotron_h", preset="tiny", block_size=4, num_blocks=96,
        max_model_len=48, max_batch_size=4, prefill_chunk_size=8, seed=0))
    assert set(nemotron.stats()["state"]) == set(st)


def test_sixty_four_lanes_with_a_float32_state_decode_in_one_program():
    """The cell's lane count: 70 requests on 64 lanes, every one finished,
    decode programs of 64 rows among them, every slot reset once a request
    and every page given back."""
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


def test_a_dict_of_fields_is_laid_over_the_preset():
    """How a configuration file gives the seeded distribution: the
    published preset keeps its `initializer_range`."""
    assert GraniteHybridConfig.h_small().initializer_range == 0.02
    fields = {"initializer_range": 0.05, "embedding_range": 0.01,
              "final_norm_init": 4.0}
    engine = _engine(model_config=fields)
    assert engine.model_cfg == dataclasses.replace(CFG, **fields)
    tree = engine.runner.params
    assert 0.04 < np.asarray(tree["layers"][1]["router"]).std() < 0.06
    assert 0.008 < np.asarray(tree["wte"]).std() < 0.012
    assert (np.asarray(tree["norm"]) == 4.0).all()
    assert _engine().model_cfg == CFG


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


def test_requests_through_serve_run(llm_cluster):
    """`serve.run(build_llm_app(model="granite_hybrid", preset="tiny"))`:
    as many requests as lanes stream at once, each the tokens the engine
    gives it alone, and the replica drains."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app
    from ray_tpu.util import state

    lanes = 3
    conf = {"block_size": 8, "num_blocks": 64, "max_model_len": 64,
            "max_batch_size": lanes, "prefill_chunk_size": 16,
            "enable_prefix_cache": True}
    handle = serve.run(build_llm_app(model="granite_hybrid", preset="tiny",
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
        alone = LLMEngine(EngineConfig(model="granite_hybrid",
                                       preset="tiny", **conf))
        for p, final in zip(prompts, finals):
            assert final["done"] and final["finish_reason"] == "length"
            assert final["token_ids"] == alone.generate(
                p, SamplingParams(max_tokens=5), drive=True)["token_ids"]
        (stats,) = state.llm_status("llm")
        assert stats["running"] == 0 and stats["blocks_used"] == 0
        assert stats["state"]["resets"] == lanes
        assert stats["state"]["prefix_declined"] is True
    finally:
        serve.delete("llm")


# --------------------------------------------------------------------------
# layer parity (benchmark/parity_granite_hybrid.py): what decides `correct`
# in the benchmark's cell beside the log-prob tolerance. In float32 a sound
# program reads 1e-6; the limits here stand where the cell's stand to its
# bf16 readings, a few times a sound reading.

PARITY_LIMITS = {"mixer_mamba": 1e-4, "mixer_attention": 1e-4,
                 "ffn_experts": 1e-4, "state_ssm": 1e-4, "routing": 0.02}


def _parity_config(limits=PARITY_LIMITS):
    return {**{k: list(v) if isinstance(v, tuple) else v
               for k, v in ARCH.items()},
            "model": {"config": "ray_tpu.models.granite_hybrid:"
                                "GraniteHybridConfig.tiny"},
            "engine": {"model_config": {}, "prefill_chunk_size": 32},
            "layer_parity": {"rows": 75, "limits": limits}}


@pytest.mark.parametrize("fault, program, low, over", [
    ("sound", {}, {}, set()),
    ("the program adds the halves whole", {"residual_multiplier": 1.0}, {},
     set()),  # a half-layer's own output is the same: the log-prob's to see
    ("the program scales the scores by 1 / sqrt(hd)",
     {"attention_multiplier": 0.25}, {}, {"mixer_attention"}),
    ("the program takes its experts for 4-9", {"expert_offset": 4}, {},
     {"ffn_experts"}),
    ("the program lets a token choose 2", {"num_experts_per_tok": 2}, {},
     {"ffn_experts", "routing"}),
    ("the recurrent state in bfloat16", {}, {"state_dtype": jnp.bfloat16},
     {"state_ssm", "mixer_mamba"}),
    ("float8 operands", {}, {"operand_dtype": jnp.float8_e4m3fn},
     {"mixer_mamba", "mixer_attention", "ffn_experts", "state_ssm"}),
    ("the reference drops the state at the chunk's edge", {},
     {"drop_state_at": 32}, {"mixer_mamba", "state_ssm"}),
    ("the routed experts left out", {}, {"reference_params": "no we_down"},
     {"ffn_experts"}),
])
def test_layer_parity_tells_a_fault_from_rounding(params, tokens, fault,
                                                  program, low, over):
    from benchmark import parity_granite_hybrid as parity

    cfg = dataclasses.replace(CFG, **program)
    if "reference_params" in low:  # the reference's side without them
        low = {"reference_params": {**params, "layers": [
            {**p, "we_down": jnp.zeros_like(p["we_down"])}
            for p in params["layers"]]}}
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


BOTH = {"edge_logprob", "edge_state"}


@pytest.mark.parametrize("fault, break_engine, low, over", [
    ("sound", None, {}, set()),
    ("the reference drops the state at the edge", None,
     {"drop_state_at": 32}, BOTH),
    ("every chunk starts its slot from zero", _every_chunk_starts_fresh, {},
     BOTH),
    ("the reference's state in bfloat16", None,
     {"state_dtype": jnp.bfloat16}, {"edge_state"}),
])
def test_the_engines_leg_sees_what_happens_at_a_chunks_edge(
        tokens, fault, break_engine, low, over):
    """`parity_granite_hybrid.serve_edge` / `edge_parity`: the engine
    itself on three prompts at once that end just past a chunk's edge, its
    log-probs and the SSM state left in its slots against the
    reference's."""
    from benchmark import parity_granite_hybrid as parity

    engine = _edge_engine()
    if break_engine:
        break_engine(engine)
    served, slots = parity.serve_edge(engine, tokens, 32, drive=True)
    assert [len(c["prompt"]) for c in served] == [33, 34, 65]
    assert slots.shape == (3, 4, 8, 16, 16)
    readings = parity.edge_parity(engine.runner.params, served, slots, ARCH,
                                  **low)
    assert {k for k, v in readings.items() if v > 1e-4} == over, \
        (fault, readings)
    if not over:
        assert max(readings.values()) < 2e-5, readings
        state = engine.stats()["state"]
        assert (state["resets"], state["carried"]) == (3, 4)


def test_a_layer_out_of_parity_fails_the_cells_comparison(
        tokens, tmp_path, monkeypatch, capsys):
    """`parity_granite_hybrid.serve_reference`, what the configuration
    names: the plain reference's log-probs where every half-layer and the
    engine that serves the weights are within their limits, and out of any
    tolerance where one is not."""
    from benchmark import parity_granite_hybrid as parity

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
    limits = {**PARITY_LIMITS, "edge_logprob": 1e-4, "edge_state": 1e-4}
    path = tmp_path / "config.json"
    monkeypatch.setattr(ref, "_CONFIG", str(path))
    try:
        path.write_text(json.dumps(_parity_config(limits)))
        plain = ref.serve_reference(params, None, cases)
        assert parity.serve_reference(params, None, cases) == plain
        assert "within limits" in capsys.readouterr().out
        for name in ("state_ssm", "edge_state"):
            path.write_text(json.dumps(_parity_config(
                {**limits, name: -1.0})))
            failed = parity.serve_reference(params, None, cases)
            assert f"FAILED: {name}" in capsys.readouterr().out
            assert all(abs(a - b - parity.FAILED) < 1e-3
                       for x, y in zip(plain, failed) for a, b in zip(x, y))
    finally:
        stop.set()
        stepping.join()
