"""The nemotron_h family (`NemotronHConfig.tiny`: Mamba-2, attention and
routed-expert blocks in the order ``MEM*EME``, 8 of 16 experts held, one
shared) against the plain reference the benchmark compares with on the
chip (`benchmark/reference_nemotron_h.py`), on seeded random weights, and
what its recurrent state asks of the serve engine.

Logits are compared, not sampled tokens. TOL: system and reference do the
same float32 arithmetic in another order (the chunked form against the
token-by-token recurrence, cached context and carried state against one
full pass), which moves a logit of magnitude 0.1-0.6 by 2.4e-7 here; 2e-5
leaves room for a platform's reduction order, and every mutation measured
(on the reference, against itself) moves the logits past it: the recurrent
state held in bfloat16 5.8e-5, bfloat16 matrix-product operands 8.9e-3, no
selection bias 2.0e-2, no scaling factor 2.0e-2, no renormalisation of the
chosen weights 2.5e-2, one expert fewer a token 2.8e-2, the shared expert
left out 4.0e-2, the held range one expert off 5.0e-2, the conv taps in
another order 4.0e-1."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_nemotron_h as ref
from ray_tpu.models import moe
from ray_tpu.models.nemotron_h import NemotronHConfig, init_nemotron_h
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

TOL = 2e-5
CFG = NemotronHConfig.tiny()


def _arch(cfg):
    return {"hybrid_override_pattern": cfg.layer_pattern,
            "num_hidden_layers": cfg.n_layer,
            "mamba_num_heads": cfg.mamba_num_heads,
            "mamba_head_dim": cfg.mamba_head_dim,
            "ssm_state_size": cfg.ssm_state_size, "n_groups": cfg.n_groups,
            "conv_kernel": cfg.conv_kernel,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "expert_offset": cfg.expert_offset,
            "layer_norm_epsilon": cfg.layer_norm_epsilon,
            "vocab_size": cfg.vocab_size}


ARCH = _arch(CFG)


def _seeded(cfg, seed=7):
    p = init_nemotron_h(jax.random.PRNGKey(seed), cfg)
    # norm scales and the skip away from 1, so that one left out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in p["layers"]:
        for name in ("norm", "gate_norm", "D"):
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
        jax.random.PRNGKey(9), (48,), 1, CFG.vocab_size), np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    return np.asarray(ref.forward(params, jnp.asarray(tokens), ARCH)[0])


def _worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _runner(params, **kw):
    args = dict(block_size=8, num_blocks=24, max_model_len=64,
                max_batch_size=4, prefill_chunk_size=16)
    args.update(kw)
    return ModelRunner(adapters()["nemotron_h"], CFG, params, **args)


def _engine(**overrides):
    kw = dict(model="nemotron_h", preset="tiny", block_size=4,
              num_blocks=96, max_model_len=48, max_batch_size=4,
              prefill_chunk_size=8, seed=0)
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _state(runner):
    return jax.tree.map(np.asarray, runner.state)


def test_the_adapter_says_what_the_family_caches():
    ad = adapters()["nemotron_h"]
    assert [k.layers for k in ad.kv_kinds(CFG)] == [1] and CFG.n_layer == 7
    layers, parts = ad.state_fn(CFG)
    assert layers == 3
    # the SSM state in float32 whatever the compute dtype (`assumed`)
    assert {n: jnp.dtype(d) for n, _, d in
            NemotronHConfig.nano_30b_a3b_l18_ep4().state_parts()}["ssm"] \
        == jnp.float32
    conv = (8 * 8 + 2 * 2 * 16,)
    assert dict((n, s) for n, s, _ in parts) == {
        "conv0": conv, "conv1": conv, "conv2": conv, "ssm": (8, 8, 16)}
    for name in ("gpt2", "llama"):
        assert adapters()[name].state_fn is None


def test_the_published_preset_is_the_published_model():
    full = NemotronHConfig.nano_30b_a3b()
    assert (full.n_layer, full.n_ssm_layers, full.n_kv_layers) == (52, 23, 6)
    assert full.layer_pattern.count("E") == 23
    cut = NemotronHConfig.nano_30b_a3b_l18_ep4()
    assert cut.layer_pattern == "MEMEM*EMEMEM*EMEME"
    assert (cut.n_ssm_layers, cut.n_kv_layers) == (8, 2)
    shapes = jax.eval_shape(
        lambda: init_nemotron_h(jax.random.PRNGKey(0), cut))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_249_672_576  # ISSUE 32's arithmetic
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype(jnp.bfloat16)}
    same = {f.name for f in dataclasses.fields(cut)} - {
        "layer_pattern", "experts_held", "vocab_size",
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
    four decode steps. The attention layers read the cached context as
    these toy rows make it (one tile holds the table), and in tiles of one
    and two pages, so that chunks start at tile edges and decode steps
    read a part of the table."""
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


def test_engine_logprobs_match_the_reference(params, tokens):
    """Prefill (chunked), then decode through the engine, overlapped loop
    and all: the streamed log-probs against the reference's one full
    forward over prompt + streamed tokens."""
    e = LLMEngine(EngineConfig(
        model="nemotron_h", preset="tiny", block_size=8, num_blocks=24,
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


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """One expert layer: each of four chips holds 4 of the router's 16
    experts; their routed parts, with the shared expert counted once, add
    up to what the uncut reference gives for the whole layer. The program
    is given each share in turn through `routed_experts(held=)`."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = _seeded(whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        uncut, _ = ref.expert_mixer(h, p32, _arch(whole), jnp.matmul,
                                    lambda a: a)
    total, counts = 0.0, []
    for chip in range(4):
        def expert_fn(rows, mm, chip=chip):
            a = jax.nn.relu(mm(rows, p["we_up"][4 * chip:4 * chip + 4]))
            return mm(a * a, p["we_down"][4 * chip:4 * chip + 4])

        def shared(rows):
            a = jax.nn.relu(rows @ p["ws_up"])
            return (a * a) @ p["ws_down"]

        y, c, _ = moe.routed_experts(
            h, p["router"], expert_fn, k=CFG.num_experts_per_tok,
            norm_topk=True, score="sigmoid", select_bias=p["router_bias"],
            scale=CFG.routed_scaling_factor, held=(4 * chip, 4),
            shared=shared if chip == 0 else None)
        total = total + y
        counts.append(np.asarray(c))
    assert _worst(total, uncut) < TOL
    # the router's load is the model's, whatever is held
    assert all((c == counts[0]).all() for c in counts)
    assert counts[0].sum() == 24 * CFG.num_experts_per_tok


def test_selection_bias_chooses_and_does_not_weigh():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros((8,)).at[5].set(10.0)  # expert 5 always chosen
    w, e, counts, s = moe.route(x, router, 2, True, score="sigmoid",
                                select_bias=bias, scale=2.5)
    assert (np.asarray(e)[:, 0] == 5).all() and counts[5] == 6
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(np.asarray(s), np.asarray(e), 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-6)


def test_routed_experts_as_olmoe_calls_it_lowers_to_the_same_program():
    """`held` = all, softmax, no bias, no scale, none shared, spelled out
    or left out: the same HLO."""
    x = jnp.zeros((8, 32), jnp.bfloat16)
    router = jnp.zeros((32, 8), jnp.bfloat16)
    wg = jnp.zeros((8, 32, 16), jnp.bfloat16)
    wd = jnp.zeros((8, 16, 32), jnp.bfloat16)

    def fn(rows, mm):
        return mm(jax.nn.silu(mm(rows, wg)), wd)

    def plain(x):
        return moe.routed_experts(x, router, fn, k=2, norm_topk=False)

    def spelled(x):
        return moe.routed_experts(
            x, router, fn, k=2, norm_topk=False, score="softmax",
            select_bias=None, scale=1.0, held=(0, 8), shared=None)

    assert jax.jit(plain).lower(x).as_text() \
        == jax.jit(spelled).lower(x).as_text().replace("spelled", "plain")


def test_padded_rows_and_idle_lanes_leave_a_state_bit_equal(params, tokens):
    """Padded rows: the same 13 tokens through chunks of 8 + 5 rows (the
    second padded to 8) and through one program of 16 rows (3 padded)
    leave the same conv window to the bit and the same SSM state to
    rounding, and a program without a slot (warm-up) writes nothing. Idle
    lanes: a decode step leaves every slot that is not a lane of it, and
    what its padded lanes point at, equal to the bit."""
    a, b = _runner(params, prefill_chunk_size=8), _runner(params)
    table = [3, 7]
    a.prefill_chunk(tokens[:8].tolist(), 0, table, 0.0)  # slot -1: no-op
    assert all((v == 0).all() for v in _state(a).values())
    a.collect(a.launch_chunk(tokens[:8].tolist(), 0, table, 0.0, slot=1))
    a.collect(a.launch_chunk(tokens[8:13].tolist(), 8, table, 0.0, slot=1))
    b.collect(b.launch_prefill(tokens[:13].tolist(), table, 0.0, slot=1))
    sa, sb = _state(a), _state(b)
    # the first Mamba layer sees the same inputs to the bit, the deeper
    # ones the same to rounding (another program computed them)
    for name in ("conv0", "conv1", "conv2"):
        np.testing.assert_array_equal(sa[name][0], sb[name][0])
        np.testing.assert_allclose(sa[name], sb[name], atol=1e-6)
    np.testing.assert_allclose(sa["ssm"], sb["ssm"], atol=1e-6)
    assert np.abs(sa["ssm"][:, 1]).max() > 0
    for name in sa:  # other slots untouched
        assert (np.delete(sa[name], 1, axis=1) == 0).all()
    # three lanes decode in a 4-lane bucket: slot 1 moves, the others do not
    a.collect(a.launch_prefill(tokens[:9].tolist(), [5, 6], 0.0, slot=3))
    before = _state(a)
    a.decode([DecodeItem(int(tokens[13]), 13, table, 0.0, slot=1),
              DecodeItem(5, 0, [0], 0.0), DecodeItem(6, 0, [0], 0.0)])
    after = _state(a)
    for name in before:
        assert (after[name][:, 1] != before[name][:, 1]).any()
        for idle in (0, 2, 3):
            np.testing.assert_array_equal(after[name][:, idle],
                                          before[name][:, idle])


def test_rows_with_dt_zero_do_not_move_the_ssm_state():
    """Bucket padding is ``dt = 0``: a padded chunk behind the real rows
    leaves the state they left, to the bit."""
    from ray_tpu.models.nemotron_h import ssd_chunked

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (16, 8, 8))
    B, C = (jax.random.normal(kk, (16, 2, 16)) for kk in k[1:3])
    dt = jax.nn.softplus(jax.random.normal(k[3], (16, 8)))
    A = -jnp.exp(jax.random.normal(k[4], (8,)))
    S0 = jnp.ones((8, 8, 16))
    y, S = ssd_chunked(x[:8], B[:8], C[:8], dt[:8], A, S0, 8)
    y2, S2 = ssd_chunked(x, B, C, dt.at[8:].set(0.0), A, S0, 8)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2[:8]))
    assert np.abs(np.asarray(S) - 1.0).max() > 0.1


def test_a_reused_slot_starts_from_zero(params, tokens, want):
    r = _runner(params)
    r.collect(r.launch_prefill(tokens[20:36].tolist(), [4, 5], 0.0, slot=0))
    assert np.abs(_state(r)["ssm"][:, 0]).max() > 0
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
        assert turns < 3000
    while engine.step():
        pass
    return [s.final() for s in streams]


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
    assert st["state"]["slots"] == 4 and st["state"]["bytes"] > 0
    # a family without recurrent state has nothing to report
    assert LLMEngine(EngineConfig(
        model="llama", preset="olmoe_tiny", block_size=4, num_blocks=32,
        max_model_len=32, max_batch_size=2)).stats()["state"] == {}


def test_speculation_is_refused_for_a_stateful_family():
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(speculative={"method": "ngram", "num_draft_tokens": 2})


def test_the_routing_account_counts_pairs_on_held_experts():
    e = _engine()
    e.generate(_prompts((10,))[0], SamplingParams(max_tokens=4), drive=True)
    moe_stats = e.stats()["moe"]
    for kind in ("prefill", "decode"):
        acc = moe_stats[kind]
        assert len(acc["expert_pairs"]) == CFG.n_routed_experts
        lo, n = CFG.expert_offset, CFG.experts_held
        assert acc["held_pairs"] == sum(acc["expert_pairs"][lo:lo + n])
        assert 0 < acc["held_pairs"] < acc["pairs"]


# --------------------------------------------------------------------------
# layer parity (benchmark/parity_nemotron_h.py): what decides `correct` in
# the benchmark's cell beside the log-prob tolerance. In float32 a sound
# program reads 1e-6; the limits here stand where the cell's stand to its
# bf16 readings, a few times a sound reading.

PARITY_LIMITS = {"mixer_M": 1e-4, "mixer_E": 1e-4, "mixer_*": 1e-4,
                 "state_M": 1e-4, "routing_E": 0.02}


def _parity_config(limits=PARITY_LIMITS):
    return {**ARCH, "model": {
        "config": "ray_tpu.models.nemotron_h:NemotronHConfig.tiny"},
        "engine": {"model_config": {}},
        "layer_parity": {"rows": 41, "limits": limits}}


@pytest.mark.parametrize("fault, program, low, over", [
    ("sound", {}, {}, set()),
    ("the program scales the routed sum by 1", {"routed_scaling_factor": 1.0},
     {}, {"mixer_E"}),
    ("the program takes its experts for 5-12", {"expert_offset": 5}, {},
     {"mixer_E"}),
    ("the program lets a token choose 2", {"num_experts_per_tok": 2}, {},
     {"mixer_E", "routing_E"}),
    ("the program does not renormalise", {"norm_topk_prob": False}, {},
     {"mixer_E"}),
    ("the recurrent state in bfloat16", {}, {"state_dtype": jnp.bfloat16},
     {"state_M"}),
    ("float8 operands", {}, {"operand_dtype": jnp.float8_e4m3fn},
     {"mixer_M", "mixer_E", "mixer_*", "state_M"}),
    ("the routed experts left out", {}, {"reference_params": "no we_down"},
     {"mixer_E"}),
])
def test_layer_parity_tells_a_fault_from_rounding(params, tokens, fault,
                                                  program, low, over):
    from benchmark import parity_nemotron_h as parity

    cfg = dataclasses.replace(CFG, **program)
    if "reference_params" in low:  # the reference's side without them
        low = {"reference_params": {**params, "layers": [
            {**p, "we_down": jnp.zeros_like(p["we_down"])}
            if "we_down" in p else p for p in params["layers"]]}}
    readings = parity.layer_parity(params, tokens[:41], cfg, ARCH, **low)
    got = {k for k, limit in PARITY_LIMITS.items() if readings[k] > limit}
    assert got == over, (fault, readings)
    if not over:  # float32 against float32: an order of operations apart
        assert max(readings.values()) < 2e-5, readings


def test_a_layer_out_of_parity_fails_the_cells_comparison(
        params, tokens, tmp_path, monkeypatch, capsys):
    """`parity_nemotron_h.serve_reference`, what the configuration names:
    the plain reference's log-probs where every layer is within its
    limits, and out of any tolerance where one is not."""
    import json

    from benchmark import parity_nemotron_h as parity

    cases = [{"prompt": tokens[:42].tolist(), "tokens": tokens[42:46].tolist()},
             {"prompt": tokens[:9].tolist(), "tokens": tokens[9:12].tolist()}]
    path = tmp_path / "config.json"
    monkeypatch.setattr(ref, "_CONFIG", str(path))
    path.write_text(json.dumps(_parity_config()))
    plain = ref.serve_reference(params, None, cases)
    assert parity.serve_reference(params, None, cases) == plain
    assert "within limits" in capsys.readouterr().out
    path.write_text(json.dumps(_parity_config(
        {**PARITY_LIMITS, "state_M": 0.0})))
    failed = parity.serve_reference(params, None, cases)
    assert "FAILED: state_M" in capsys.readouterr().out
    assert all(abs(a - b - parity.FAILED) < 1e-3
               for x, y in zip(plain, failed) for a, b in zip(x, y))


def test_a_dict_of_fields_is_laid_over_the_preset():
    """How a configuration file gives the seeded distribution: the
    published preset keeps the published `initializer_range`."""
    assert NemotronHConfig.nano_30b_a3b().initializer_range == 0.02
    engine = _engine(model_config={"initializer_range": 0.05})
    assert engine.model_cfg == dataclasses.replace(
        CFG, initializer_range=0.05)
    router = np.asarray(engine.runner.params["layers"][1]["router"])
    assert 0.04 < router.std() < 0.06
    assert _engine().model_cfg == CFG
