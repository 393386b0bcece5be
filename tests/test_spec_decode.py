"""Speculative decoding + the paged context read (ISSUE 19, 41): the
invariant matrix.

Speculation is a pure *throughput* transform — every test here pins the
semantics side of that claim: greedy outputs bit-identical spec-on vs
spec-off (both model families, the context read by the tile loops and by
the Pallas kernel), and the speculative path composing with every other
serving feature without changing outputs: preemption-recompute,
prefix-cache warm hits, mid-stream replica kill (failover replay),
update_weights hot-swap, and page-refcount hygiene when drafts get
rejected. The kernel (ops/paged_attention.py; what a decode and a verify
step read their context with on a TPU, forced here through the one
predicate that picks the path, conftest `read_by_kernel`) gets its own
parity gates (kernel-level vs the full-width reference, engine-level vs
the loops) at atol 1e-4.
"""

import dataclasses
import sys
import threading

import cloudpickle
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm import (
    EngineConfig,
    LLMEngine,
    NGramProposer,
    SamplingParams,
    SpeculativeConfig,
)

cloudpickle.register_pickle_by_value(sys.modules[__name__])


# ------------------------------------------------------------- proposer


def test_ngram_proposer_prompt_lookup():
    p = NGramProposer()
    # trailing [5, 6] seen earlier, continuation follows it
    assert p.propose([1, 5, 6, 7, 8, 9, 5, 6], 4) == [7, 8, 9, 5]
    # most RECENT earlier occurrence wins
    assert p.propose([5, 6, 1, 5, 6, 2, 5, 6], 2) == [2, 5]
    # no earlier occurrence of any trailing n-gram: no draft
    assert p.propose([1, 2, 3, 4, 5], 3) == []
    # the copy is self-extending: a period-1 cycle yields the full k
    # even though only one real token follows the matched n-gram
    assert p.propose([7, 7, 7, 7], 2) == [7, 7]
    assert p.propose([7, 7, 7, 7], 6) == [7] * 6
    # period-2 cycle extends with the right phase
    assert p.propose([9, 4, 9, 4, 9, 4], 5) == [9, 4, 9, 4, 9]
    assert p.propose([], 4) == []


def test_speculative_config_validation():
    assert SpeculativeConfig.from_payload(None) is None
    cfg = SpeculativeConfig.from_payload({"num_draft_tokens": 3})
    assert cfg.num_draft_tokens == 3 and cfg.method == "ngram"
    same = SpeculativeConfig(num_draft_tokens=2)
    assert SpeculativeConfig.from_payload(same) is same
    with pytest.raises(ValueError):
        SpeculativeConfig.from_payload({"num_draft_tokens": 0})
    with pytest.raises(ValueError):
        SpeculativeConfig.from_payload({"bogus_key": 1})
    with pytest.raises(ValueError):
        SpeculativeConfig(num_draft_tokens=2, method="eagle")
    with pytest.raises(ValueError):
        SpeculativeConfig(num_draft_tokens=2, max_ngram=1, min_ngram=2)


def test_use_paged_attention_is_no_choice_any_more():
    """The field outlives the flag only for the benchmark's five older
    serve configurations, which carry the key as false (`from_dict`
    refuses an unknown key, and they are a `benchmark` issue's to edit;
    the three added since leave it out): false is accepted and read by
    nothing, true is refused with the reason."""
    import glob
    import json
    import os

    assert EngineConfig.from_dict({"use_paged_attention": False})
    with pytest.raises(ValueError, match="reads_by_kernel"):
        EngineConfig(use_paged_attention=True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    engines = [json.load(open(path)).get("engine") for path in sorted(
        glob.glob(os.path.join(root, "benchmark", "configs", "*.json")))]
    assert sum(e is not None for e in engines) == 9
    assert sum("use_paged_attention" in e for e in filter(None, engines)) \
        == 5
    for engine in filter(None, engines):
        EngineConfig.from_dict({"model": "gpt2", **engine})


# ------------------------------------------------------- kernel parity


def test_paged_attention_kernel_matches_dense_reference():
    """The kernel-level gate at the engine tests' toy pool (pages of 4
    slots): pallas (interpret mode on CPU) vs the full-width oracle,
    covering W=1 (decode) and W=5 (verify window, causal in its own
    rows), GQA head grouping, and the ctx_len edges (0 = nothing cached,
    full = every mapped slot valid). tests/test_paged_attention.py runs
    it at the serve configurations' head shapes."""
    from ray_tpu.ops.context_attention import causal_rows
    from ray_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )
    from ray_tpu.serve.llm.cache import KVLayout

    rng = np.random.RandomState(0)
    S, H, HK, D, bs, maxB, npages = 3, 4, 2, 16, 4, 6, 32
    layout = KVLayout(1, npages, bs, HK, D)  # a pool of one layer
    k_pages = rng.normal(size=layout.shape).astype(np.float32)
    v_pages = rng.normal(size=layout.shape).astype(np.float32)
    perm = rng.permutation(np.arange(1, npages))
    tables = perm[:S * maxB].reshape(S, maxB).astype(np.int32)
    ctx_len = np.asarray([0, 7, maxB * bs], np.int32)  # the edges
    for W in (1, 5):
        q = rng.normal(size=(S, W, HK, H // HK, D)).astype(np.float32)
        ok = rng.normal(size=(S, W, HK, D)).astype(np.float32)
        ov = rng.normal(size=(S, W, HK, D)).astype(np.float32)
        operands = (q, ok, ov, causal_rows(jnp.ones((S, W), bool)),
                    jnp.asarray(k_pages), jnp.asarray(v_pages),
                    jnp.asarray(tables), jnp.asarray(ctx_len))
        out = paged_attention(*operands, layout=layout, layer=0,
                              dtype=jnp.float32, interpret=True)
        ref = paged_attention_reference(*operands, layout=layout, layer=0,
                                        dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


# ----------------------------------------------------------- engine level


def _engine(model="gpt2", num_blocks=64, *, spec=None,
            max_batch_size=4, chunk=256, prefix_cache=True, seed=0):
    if model == "gpt2":
        from ray_tpu.models import gpt2

        cfg = dataclasses.replace(gpt2.GPT2Config.tiny(),
                                  dtype=jnp.float32, remat=False)
    else:
        from ray_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
    return LLMEngine(EngineConfig(
        model=model, model_config=cfg, block_size=4,
        num_blocks=num_blocks, max_model_len=32,
        max_batch_size=max_batch_size, seed=seed,
        prefill_chunk_size=chunk, enable_prefix_cache=prefix_cache,
        speculative=spec))


def _drive(engine, streams):
    import time

    deadline = time.monotonic() + 120
    while any(s.final() is None for s in streams):
        if not engine.step():
            pass
        assert time.monotonic() < deadline, "engine made no progress"
    return [s.final() for s in streams]


def _repetitive_prompt(seed, n=12):
    """A motif-tiled prompt: the shape the n-gram proposer predicts."""
    rng = np.random.RandomState(seed)
    motif = rng.randint(1, 500, size=4).tolist()
    return (motif * ((n + 3) // 4))[:n]


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_spec_greedy_bit_identical_all_attention_paths(model,
                                                       read_by_kernel):
    """THE spec gate, both families: greedy output is bit-identical
    across {spec off, spec on} x {the loops, the kernel}, and the
    spec arms actually exercised the verify program, the kernel arms
    the kernel: in every decode and every verify launch."""
    prompt = _repetitive_prompt(3)
    sp = SamplingParams(max_tokens=16)
    read_by_kernel(False)
    want = _engine(model).generate(prompt, sp, drive=True)["token_ids"]
    assert len(want) == 16
    for label, spec, paged in (
            ("spec", {"num_draft_tokens": 4}, False),
            ("paged", None, True),
            ("spec+paged", {"num_draft_tokens": 4}, True)):
        read_by_kernel(paged)
        eng = _engine(model, spec=spec)
        got = eng.generate(prompt, sp, drive=True)["token_ids"]
        assert got == want, f"{model}/{label} diverged from plain greedy"
        st = eng.stats()
        if spec:
            assert st["spec_proposed"] > 0, \
                f"{model}/{label}: verify program never ran"
            assert st["spec_accepted"] > 0, \
                f"{model}/{label}: nothing accepted on a cyclic prompt"
        by = st["context_by_kind"]["full"]
        ran = {kind: by[kind]["kernel_steps"] for kind in by}
        if paged:
            assert ran["decode"] + ran["verify"] > 0 == ran["prefill"], ran
            assert ran["verify"] > 0 or not spec
            # whole pages to the lane's own length, no tile of the longest
            assert all(0 <= by[k]["slots_read"] - by[k]["slots_valid"]
                       < 4 * max(1, ran[k]) for k in ("decode", "verify"))
        else:
            assert not any(ran.values()), ran


def test_spec_with_preemption_recompute_bit_identical():
    """Spec x preemption: a pool too small for both sequences preempts
    one mid-decode; recompute-resume under speculation still produces
    exactly the unconstrained spec-off outputs."""
    prompts = [_repetitive_prompt(5, n=10), _repetitive_prompt(6, n=11)]
    sp = SamplingParams(max_tokens=12)
    want = [_engine(num_blocks=64).generate(p, sp, drive=True)
            ["token_ids"] for p in prompts]

    tight = _engine(num_blocks=11, spec={"num_draft_tokens": 4})
    streams = [tight.add_request(p, sp) for p in prompts]
    finals = _drive(tight, streams)
    assert tight.scheduler.preemption_count > 0, \
        "pool was sized to force preemption"
    for f, expect in zip(finals, want):
        assert f["token_ids"] == expect, \
            "speculative sequence diverged after preemption-requeue"
    assert tight.stats()["blocks_used"] == 0


def test_spec_with_prefix_cache_warm_hit_bit_identical():
    """Spec x prefix cache: a warm admission (shared pages, prefill
    skipped) followed by speculative decode matches the cold run, and
    the hit is real (cached_tokens > 0)."""
    rng = np.random.RandomState(41)
    shared = _repetitive_prompt(8, n=16)  # 4 full pages
    suffixes = [rng.randint(1, 500, size=3).tolist() for _ in range(2)]
    sp = SamplingParams(max_tokens=10)
    want = [_engine(num_blocks=96, chunk=8).generate(
        shared + sfx, sp, drive=True)["token_ids"] for sfx in suffixes]

    eng = _engine(num_blocks=96, chunk=8, spec={"num_draft_tokens": 4})
    got, cached = [], []
    for sfx in suffixes:
        fin = eng.generate(shared + sfx, sp, drive=True)
        got.append(fin["token_ids"])
        cached.append(fin["cached_tokens"])
    assert got == want, "warm-prefix speculative output diverged"
    assert cached[0] == 0 and cached[1] == 16, cached
    assert eng.stats()["spec_accepted"] > 0


def test_spec_rejected_runs_leak_no_pages():
    """Rejected drafts must not leak pages: rejected window slots stay
    mere garbage past the frontier, and after every stream retires the
    pool is back to zero pages used. Hot-temperature sampling forces
    the rejections — the proposer copies history, the target samples
    near-uniform over 512 tokens, so drafts die at the first mismatch
    (a greedy tiny model would just keep agreeing with its own loop)."""
    rng = np.random.RandomState(43)
    eng = _engine(num_blocks=96, spec={"num_draft_tokens": 8})
    # random prompts with an internal repeat so the proposer FINDS a
    # draft (a proposal must happen for a rejection to happen)
    prompts = []
    for _ in range(4):
        half = rng.randint(1, 500, size=5).tolist()
        prompts.append(half + half)
    streams = [eng.add_request(p, SamplingParams(max_tokens=8,
                                                 temperature=1.5))
               for p in prompts]
    finals = _drive(eng, streams)
    assert all(f["num_generated"] == 8 for f in finals)
    st = eng.stats()
    assert st["spec_proposed"] > 0, "no drafts were ever proposed"
    assert st["spec_accepted"] < st["spec_proposed"], \
        "expected at least one rejected draft token on random prompts"
    assert st["blocks_used"] == 0, \
        "rejected speculative runs leaked page refs"
    assert st["waiting"] == 0 and st["running"] == 0


def test_spec_with_weight_hot_swap_mid_generation():
    """Spec x update_weights: a hot-swap lands between speculative
    steps (never inside one), every stream completes its budget, and
    the per-token version tags stay monotonic — multi-token commits
    must tag every committed token with the version its verify step
    ran on."""
    from ray_tpu.serve.llm.runner import adapters

    eng = _engine(num_blocks=96, max_batch_size=8,
                  spec={"num_draft_tokens": 4})
    sp = SamplingParams(max_tokens=16, logprobs=True)
    streams = [eng.add_request(_repetitive_prompt(50 + i), sp)
               for i in range(8)]
    # all admitted, a spec step or two in — but well short of the 16-
    # token budget: at ~K+1 tokens per verify commit the streams race
    # to completion, and the swap must land while all 8 are in flight
    for _ in range(3):
        eng.step()
    new_params = adapters()["gpt2"].init_fn(jax.random.PRNGKey(7),
                                            eng.model_cfg)
    stats = eng.update_weights(1, new_params)
    assert stats["in_flight_streams"] == 8
    finals = _drive(eng, streams)
    assert all(f is not None and f["done"] for f in finals)
    assert all(f["num_generated"] == 16 for f in finals)
    swapped = [f for f in finals if len(f["weight_versions"]) > 1]
    assert swapped, "swap landed after every stream finished"
    for f in swapped:
        assert f["stale"], "mid-generation swap must tag the stream"
        assert f["weight_versions"] == sorted(set(f["weight_versions"]))
    assert eng.stats()["spec_proposed"] > 0
    assert eng.stats()["blocks_used"] == 0


def test_spec_stream_indices_contiguous_with_logprobs():
    """Multi-token commits emit one event per token with explicit,
    contiguous indices, and logprob events line up with their token
    (the base-index arithmetic gate)."""
    eng = _engine(num_blocks=64, spec={"num_draft_tokens": 4})
    stream = eng.add_request(_repetitive_prompt(9),
                             SamplingParams(max_tokens=12,
                                            logprobs=True))
    _drive(eng, [stream])
    events = list(stream)
    final = stream.final()
    toks = [e for e in events if not e.get("done")]
    assert [e["index"] for e in toks] == list(range(12))
    assert [e["token"] for e in toks] == final["token_ids"]
    assert len(final["logprobs"]) == 12
    assert all(np.isfinite(final["logprobs"]))


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_paged_attention_engine_logit_parity(model, read_by_kernel):
    """Engine-level kernel parity beyond token identity: greedy
    logprobs from the kernel's path match the loops' to 1e-4 — the
    numerics gate argmax equality alone cannot see."""
    prompt = _repetitive_prompt(11)
    sp = SamplingParams(max_tokens=8, logprobs=True)
    read_by_kernel(False)
    dense = _engine(model).generate(prompt, sp, drive=True)
    read_by_kernel(True)
    paged = _engine(model).generate(prompt, sp, drive=True)
    assert paged["token_ids"] == dense["token_ids"]
    np.testing.assert_allclose(paged["logprobs"], dense["logprobs"],
                               atol=1e-4)


# ------------------------------------------------- failover (cluster)


@pytest.fixture(scope="module")
def spec_cluster():
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


def _tiny64_cfg():
    from ray_tpu.models import gpt2

    return gpt2.GPT2Config(
        vocab_size=64, n_layer=1, n_head=2, n_embd=32, block_size=64,
        vocab_pad_multiple=64, dtype=jnp.float32, remat=False)


def test_spec_streams_survive_replica_kill(spec_cluster):
    """Spec x failover: concurrent speculative greedy streams, one
    replica killed mid-generation — zero client-visible failures and
    outputs bit-identical to the unkilled run (the failover replay
    re-feeds prompt+generated, so committed speculative tokens must
    replay exactly)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app
    from ray_tpu.util import chaos

    n_streams, n_tok = 4, 24
    app = build_llm_app(
        model="gpt2",
        engine_config={"model_config": _tiny64_cfg(), "block_size": 8,
                       "num_blocks": 96, "max_model_len": 64,
                       "max_batch_size": 8,
                       "speculative": {"num_draft_tokens": 4}},
        num_replicas=2, max_ongoing_requests=16)
    handle = serve.run(app, name="llm-spec")
    try:
        rng = np.random.RandomState(13)
        prompts = [(rng.randint(1, 64, size=4).tolist() * 3)[:10]
                   for _ in range(n_streams)]

        def run(on_second_event=None):
            sh = handle.options(stream=True, generator_backpressure=8)
            results = [None] * n_streams
            errors: list = []
            barrier = (threading.Barrier(n_streams + 1, timeout=180)
                       if on_second_event else None)
            resume = threading.Event()
            if on_second_event is None:
                resume.set()

            def consume(i, gen):
                try:
                    evs = []
                    for r in gen:
                        evs.append(ray_tpu.get(r, timeout=180))
                        if barrier is not None and len(evs) == 2:
                            barrier.wait()
                            resume.wait(timeout=180)
                    results[i] = evs
                except Exception as e:  # noqa: BLE001
                    errors.append((i, repr(e)))

            gens = [sh.remote({"prompt": p, "max_tokens": n_tok})
                    for p in prompts]
            threads = [threading.Thread(target=consume, args=(i, g))
                       for i, g in enumerate(gens)]
            for t in threads:
                t.start()
            if barrier is not None:
                barrier.wait()
                on_second_event()
                resume.set()
            for t in threads:
                t.join(timeout=300)
            return results, errors

        ref, errors = run()
        assert not errors, errors
        want = [evs[-1]["token_ids"] for evs in ref]
        assert all(len(w) == n_tok for w in want)

        results, errors = run(
            on_second_event=lambda: chaos.kill_replica(
                "llm-spec", busiest=True))
        assert not errors, f"client-visible failures: {errors}"
        failovers = 0
        for i, evs in enumerate(results):
            assert evs is not None, f"stream {i} never finished"
            final = evs[-1]
            toks = evs[:-1]
            assert [e["index"] for e in toks] == \
                list(range(len(toks)))
            assert final["token_ids"] == want[i], \
                f"speculative stream {i} diverged after failover"
            failovers += final.get("failovers", 0)
        assert failovers >= 1, "the kill never landed on a live stream"
    finally:
        serve.delete("llm-spec")
