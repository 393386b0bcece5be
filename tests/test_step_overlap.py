"""One serve step in flight (ISSUE 31): the engine plans and launches step
n+1 before it reads step n's results, the sampled ids staying on the
device as the next program's input.

Every case runs the same requests through two engines built alike: one
as it is, and one held to the loop of before (`_never_ahead`: it plans
only once everything is read). For greedy streams the two must agree on
every token, index, log-prob (to the bit: the same programs run on the
same inputs in the same order), finish reason and weight version."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
    SpeculativeConfig,
)

MODELS = ("gpt2", "olmoe", "nemotron_h", "mimo_v2")


def _engine(model="gpt2", **overrides):
    from ray_tpu.models import gpt2

    kw = dict(block_size=4, num_blocks=96, max_model_len=48,
              max_batch_size=4, prefill_chunk_size=8, seed=0)
    if model == "gpt2":
        kw.update(model="gpt2", model_config=dataclasses.replace(
            gpt2.GPT2Config.tiny(), dtype=jnp.float32, remat=False))
    elif model == "nemotron_h":  # recurrent state beside the pages
        kw.update(model="nemotron_h", preset="tiny")
    elif model == "mimo_v2":  # two kinds of KV layer; a window of 8 whose
        # pages go back to their pool, and to other lanes, as rows are
        # planned (release is always on)
        kw.update(model="mimo_v2", preset="tiny")
    else:  # the routed-expert block through the llama path, float32
        kw.update(model="llama", preset="olmoe_tiny")
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _never_ahead(engine):
    """The loop of before: a step is planned only when nothing is in
    flight, so every program's results are read before the next is made."""
    plan = engine._plan
    engine._plan = lambda ahead: (None, False) if ahead else plan(ahead)
    return engine


def _prompts(lengths, seed=0, vocab=60):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=n).tolist() for n in lengths]


def _drive(engine, streams, limit=2000):
    turns = 0
    while any(s.final() is None for s in streams):
        assert engine.step(), "work left and step() found nothing to do"
        turns += 1
        assert turns < limit, "engine made no progress"
    while engine.step():  # a step launched behind the last one read
        pass
    return [_outcome(s) for s in streams]


def _outcome(stream):
    """What a client saw: the streamed events in order and the final."""
    events = list(stream)
    final = stream.final()
    assert [e["token"] for e in events] == final["token_ids"]
    assert [e["index"] for e in events] == list(range(len(events)))
    return {"tokens": final["token_ids"],
            "logprobs": [e.get("logprob") for e in events],
            "versions": [e.get("weight_version") for e in events],
            "finish_reason": final["finish_reason"],
            "weight_versions": final["weight_versions"],
            "stale": final["stale"], "cached_tokens": final["cached_tokens"],
            "preemptions": final["preemptions"]}


def _serve(engine, requests):
    return _drive(engine, [engine.add_request(p, sp) for p, sp in requests])


def _assert_drained(engine):
    st = engine.stats()
    assert st["running"] == st["waiting"] == st["in_flight"] == 0
    assert st["blocks_used"] == 0
    assert not engine.has_work() and not engine.step()


def _assert_counters_add_up(engine):
    st = engine.stats()
    o = st["overlap"]
    for kind in ("decode", "prefill"):
        assert o["launched_ahead"][kind] + o["launched_drained"][kind] \
            == st["steps"][kind]
    return o


MIXED = [(5, 6), (19, 9), (11, 3), (30, 12), (7, 7), (3, 10)]


@pytest.mark.parametrize("model", MODELS)
def test_mixed_prompts_chunked_prefill_match_the_drained_engine(model):
    """Six requests on four lanes, prompts of one to four chunks, every
    lane ending on its `max_tokens`: the plan knows each ending, so the
    overlapped loop runs the drained loop's programs, one turn early."""
    reqs = [(p, SamplingParams(max_tokens=m, logprobs=True))
            for p, (_, m) in zip(_prompts([n for n, _ in MIXED]), MIXED)]
    ahead, drained = _engine(model), _never_ahead(_engine(model))
    got, want = _serve(ahead, reqs), _serve(drained, reqs)
    assert got == want
    assert all(o["finish_reason"] == "length" for o in got)
    o = _assert_counters_add_up(ahead)
    assert ahead.stats()["steps"] == drained.stats()["steps"]
    assert o["discarded_tokens"] == 0
    # one program only is launched with nothing in flight: the first
    assert sum(o["launched_drained"].values()) == 1
    assert o["drains"] == {**dict.fromkeys(o["drains"], 0), "idle": 1}
    d = _assert_counters_add_up(drained)
    assert sum(d["launched_ahead"].values()) == 0
    _assert_drained(ahead)


@pytest.mark.parametrize("model", MODELS)
def test_sampled_streams_match_too(model):
    """Temperature sampling draws from a key folded with the launch
    count: the same programs in the same order draw the same ids."""
    reqs = [(p, SamplingParams(max_tokens=8, temperature=0.9, top_k=20,
                               logprobs=True))
            for p in _prompts([6, 13, 9])]
    got = _serve(_engine(model), reqs)
    assert got == _serve(_never_ahead(_engine(model)), reqs)


def test_prefix_cache_hit_matches():
    """A request that arrives once its twin is done takes the twin's
    pages: the same hit, and the same tokens behind it, in both loops."""
    prompt = _prompts([21])[0]
    sp = SamplingParams(max_tokens=5, logprobs=True)

    def run(engine):
        first = _serve(engine, [(prompt, sp)])
        again = _serve(engine, [(prompt + [7, 8, 9], sp)])
        return first + again

    got, want = run(_engine()), run(_never_ahead(_engine()))
    assert got == want
    assert got[0]["cached_tokens"] == 0 and got[1]["cached_tokens"] == 20


def test_lane_ending_on_max_model_len_is_left_out_of_the_next_step():
    """A lane that fills its context with the step in flight is known to
    end: the step behind runs without it, nothing is discarded."""
    reqs = [(p, SamplingParams(max_tokens=64, logprobs=True))
            for p in _prompts([40, 9])]
    reqs[1] = (reqs[1][0], SamplingParams(max_tokens=30, logprobs=True))
    ahead = _engine()
    got = _serve(ahead, reqs)
    assert got == _serve(_never_ahead(_engine()), reqs)
    assert got[0]["finish_reason"] == "length"
    assert 40 + len(got[0]["tokens"]) == 48  # max_model_len
    assert _assert_counters_add_up(ahead)["discarded_tokens"] == 0
    _assert_drained(ahead)


def _eos_case(model="gpt2", first=2):
    """Four lanes, one of which ends on an eos the plan cannot know:
    (requests, that lane, the index of its eos, `first` at the least:
    late enough that the step behind it is a decode step)."""
    prompts = _prompts([6, 10, 5, 12], seed=2)
    plain = _serve(_never_ahead(_engine(model)), [
        (p, SamplingParams(max_tokens=12)) for p in prompts])
    # an id that some lane samples mid-stream and not before
    lane, k = next((i, k) for i, o in enumerate(plain)
                   for k in range(first, 10)
                   if o["tokens"][k] not in o["tokens"][:k])
    sps = [SamplingParams(max_tokens=12, logprobs=True) for _ in prompts]
    sps[lane] = SamplingParams(max_tokens=12, logprobs=True,
                               eos_token_id=plain[lane]["tokens"][k])
    return list(zip(prompts, sps)), lane, k


@pytest.mark.parametrize("model", ("gpt2", "nemotron_h", "mimo_v2"))
def test_eos_mid_flight_costs_one_discarded_lane_step(model):
    """The step behind an eos ran its lane once more: that id is counted
    and dropped, no event follows the eos, and the other lanes of that
    step (the same four-lane program either way) are not disturbed, a
    stateful family's included: the ended lane's slot moved one token
    too far, theirs did not."""
    reqs, lane, k = _eos_case(model, 2 if model == "gpt2" else 6)
    ahead = _engine(model)
    got = _serve(ahead, reqs)
    assert got == _serve(_never_ahead(_engine(model)), reqs)
    assert got[lane]["finish_reason"] == "eos"
    assert len(got[lane]["tokens"]) == k + 1
    o = _assert_counters_add_up(ahead)
    assert o["discarded_tokens"] == 1
    _assert_drained(ahead)
    from ray_tpu.util.metrics import prometheus_text

    page = prometheus_text()
    for name in ("serve_llm_steps_launched_total",
                 "serve_llm_step_drains_total",
                 "serve_llm_discarded_tokens_total"):
        assert any(ln.startswith(name + "{") for ln in page.splitlines()), \
            name


def test_an_eos_in_flight_leaves_a_stateful_lanes_slot_fit_for_reuse():
    """The step behind an eos moved the ended lane's recurrent state one
    token too far. That is harmless only because the slot's next owner
    starts from zero: six requests on four lanes, so the eos lane's slot
    is handed on. The overlapped loop admits the next request one step
    later than the drained one, so the two decode it in batches of other
    sizes (other programs) now and then: the same tokens, log-probs equal
    to rounding, where a state left over would move them by tenths."""
    reqs, lane, k = _eos_case("nemotron_h", 6)
    more = [(p, SamplingParams(max_tokens=9, logprobs=True))
            for p in _prompts([7, 13], seed=5)]
    ahead = _engine("nemotron_h")
    got = _serve(ahead, reqs + more)
    want = _serve(_never_ahead(_engine("nemotron_h")), reqs + more)
    for a, b in zip(got, want):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-5)
    assert got[lane]["finish_reason"] == "eos"
    assert len(got[lane]["tokens"]) == k + 1
    o = _assert_counters_add_up(ahead)
    assert o["discarded_tokens"] == 1
    assert ahead.stats()["state"]["resets"] == 6
    _assert_drained(ahead)


def test_pages_freed_by_an_eos_are_reusable_and_the_next_hit_is_right():
    """The discarded write went to a page no one can share: the pool
    gives every page back, and the next request over the same prefix
    hits the cache and continues as the drained engine's does."""
    reqs, lane, _ = _eos_case()

    def run(engine):
        _serve(engine, reqs)
        assert engine.stats()["blocks_used"] == 0
        # fill the pool's free pages over, then come back to that lane
        _serve(engine, [(p, SamplingParams(max_tokens=20))
                        for p in _prompts([30, 30, 30, 30], seed=9)])
        return _serve(engine, [(reqs[lane][0] + [3, 4, 5], SamplingParams(
            max_tokens=6, logprobs=True))])

    got, want = run(_engine()), run(_never_ahead(_engine()))
    assert got == want
    assert got[0]["cached_tokens"] > 0


def test_abort_of_a_lane_in_flight_drops_its_result():
    reqs = [(p, SamplingParams(max_tokens=12, logprobs=True))
            for p in _prompts([6, 9, 7])]
    ahead = _engine()
    streams = [ahead.add_request(p, sp) for p, sp in reqs]
    for _ in range(6):  # all three decoding, a decode step in flight
        ahead.step()
    assert ahead.stats()["in_flight"] == 1
    emitted = streams[1]._q.qsize()  # token events so far
    ahead.abort_request(streams[1], "client_disconnected")
    o = ahead.stats()["overlap"]
    assert o["drains"]["abort"] == 1 and o["discarded_tokens"] == 1
    assert ahead.stats()["in_flight"] == 0
    final = streams[1].final()
    assert final["finish_reason"] == "client_disconnected"
    assert final["num_generated"] == emitted  # nothing after the abort
    got = _drive(ahead, [streams[0], streams[2]])
    # the others go on as if alone with each other from there: what they
    # stream is what an engine that never saw the aborted lane end streams
    want = _serve(_never_ahead(_engine()), reqs)
    assert [g["tokens"] for g in got] \
        == [want[0]["tokens"], want[2]["tokens"]]
    _assert_counters_add_up(ahead)
    _assert_drained(ahead)


def test_a_pool_that_forces_preemption_drains_and_gives_the_same_output():
    """Growing a table would need a victim whose token is in flight: the
    plan refuses, the loop reads the step and schedules as before."""
    kw = dict(num_blocks=14, max_model_len=32, enable_prefix_cache=False)
    reqs = [(p, SamplingParams(max_tokens=14, logprobs=True))
            for p in _prompts([9, 10, 8])]
    ahead = _engine(**kw)
    got = _serve(ahead, reqs)
    assert got == _serve(_never_ahead(_engine(**kw)), reqs)
    assert sum(o["preemptions"] for o in got) > 0
    o = _assert_counters_add_up(ahead)
    assert o["drains"]["preempt"] > 0 and o["discarded_tokens"] == 0
    _assert_drained(ahead)


def test_a_window_pool_that_runs_dry_drains_and_gives_the_same_tokens():
    """The same where it is the window kind's pool alone that runs dry
    under a lane's next chunk or step: the plan refuses, the loop reads
    the step in flight and schedules again. A lane is admitted only where
    that pool has its bound free, and how much is free when a plan looks
    depends on what is in flight, so the two loops may admit at different
    steps and batch their lanes differently: the tokens are the same, the
    log-probs the same to a rounding (another bucket's program)."""
    kw = dict(model="mimo_v2", num_blocks=[96, 10], enable_prefix_cache=False)
    reqs = [(p, SamplingParams(max_tokens=16, logprobs=True))
            for p in _prompts([30, 9, 28, 12])]
    ahead, drained = _engine(**kw), _never_ahead(_engine(**kw))
    got, want = _serve(ahead, reqs), _serve(drained, reqs)
    assert [o["tokens"] for o in got] == [o["tokens"] for o in want]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=1e-5)
    assert sum(o["preemptions"] for o in want) > 0
    o = _assert_counters_add_up(ahead)
    assert o["drains"]["preempt"] > 0 and o["discarded_tokens"] == 0
    _assert_drained(ahead)
    _assert_drained(drained)
    for engine in (ahead, drained):
        kv = engine.stats()["kv"]
        assert kv["window"]["pages_used"] == kv["full"]["pages_used"] == 0
        assert kv["window"]["largest_table"] <= 5


def test_update_weights_mid_stream_never_splits_a_step():
    """The swap reads the step in flight first: every program runs on
    one version from launch to collect, every token carries the version
    of the program that sampled it, the streams are tagged stale."""
    from ray_tpu.models import gpt2

    eng = _engine()
    launch, collect = eng.runner.launch_decode, eng.runner.collect
    flying, spans = {}, []

    def spy_launch(items):
        handle = launch(items)
        flying[id(handle)] = eng.weight_version
        return handle

    def spy_collect(handle):
        out = collect(handle)
        if id(handle) in flying:
            spans.append((flying.pop(id(handle)), eng.weight_version))
        return out

    eng.runner.launch_decode, eng.runner.collect = spy_launch, spy_collect
    reqs = [(p, SamplingParams(max_tokens=12, logprobs=True))
            for p in _prompts([6, 9, 7])]
    streams = [eng.add_request(p, sp) for p, sp in reqs]
    for _ in range(7):
        eng.step()
    assert eng.stats()["in_flight"] == 1
    before = [len(s._q.queue) for s in streams]
    swap = eng.update_weights(1, gpt2.init_gpt2(
        jax.random.PRNGKey(7), eng.model_cfg))
    assert swap["in_flight_streams"] == 3
    assert eng.stats()["in_flight"] == 0
    assert eng.stats()["overlap"]["drains"]["swap"] == 1
    got = _drive(eng, streams)
    assert spans and all(a == b for a, b in spans)
    assert {a for a, _ in spans} == {0, 1}
    for n_before, o in zip(before, got):
        assert o["finish_reason"] == "length" and len(o["tokens"]) == 12
        assert o["stale"] and o["weight_versions"] == [0, 1]
        assert o["versions"] == sorted(o["versions"])
        # the step the swap read was sampled on the old weights
        assert o["versions"].count(0) == n_before + 1
    _assert_counters_add_up(eng)
    _assert_drained(eng)


def test_a_configured_proposer_never_launches_ahead():
    """Drafts are read from the tokens a step commits: with a proposer
    the loop reads every step before it plans the next, as it always
    did, and greedy output is what the plain engine gives."""
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6], [9, 9, 9, 9, 9]] + _prompts([11])
    reqs = [(p, SamplingParams(max_tokens=10, logprobs=True))
            for p in prompts]
    spec = _engine(speculative=SpeculativeConfig(num_draft_tokens=3))
    got = _serve(spec, reqs)
    want = _serve(_engine(), reqs)
    assert [g["tokens"] for g in got] == [w["tokens"] for w in want]
    o = _assert_counters_add_up(spec)
    assert sum(o["launched_ahead"].values()) == 0
    steps = spec.stats()["steps"]
    assert o["drains"]["speculation"] == steps["decode"] + steps["prefill"]
    _assert_drained(spec)


def test_program_n_plus_1_is_launched_before_program_n_is_collected():
    eng = _engine()
    order, names, kept = [], {}, []

    def spy(method, tag):
        inner = getattr(eng.runner, method)

        def call(*a, **kw):
            out = inner(*a, **kw)
            if tag == "launch":
                kept.append(out)  # or a later handle takes its id
                names[id(out)] = len(names)
                order.append(("launch", names[id(out)]))
            else:
                order.append(("collect", names[id(a[0])]))
            return out
        setattr(eng.runner, method, call)

    for method in ("launch_prefill", "launch_chunk", "launch_decode"):
        spy(method, "launch")
    spy("collect", "collect")
    _serve(eng, [(p, SamplingParams(max_tokens=8))
                 for p in _prompts([5, 17, 9])])
    n = len(names)
    assert n == sum(eng.stats()["steps"].values())
    assert [i for what, i in order if what == "launch"] == list(range(n))
    assert [i for what, i in order if what == "collect"] == list(range(n))
    # work was waiting throughout: every program but the last was still
    # unread when the next one went to the device
    for i in range(n - 1):
        assert order.index(("launch", i + 1)) < order.index(("collect", i))
    # and never more than one behind the one being read
    for i in range(n - 2):
        assert order.index(("collect", i)) < order.index(("launch", i + 2))


def test_step_reports_work_until_nothing_is_in_flight():
    eng = _engine()
    assert not eng.step() and not eng.has_work()
    stream = eng.add_request([1, 2, 3], SamplingParams(max_tokens=3))
    turns = 0
    while eng.has_work():
        assert eng.step()
        turns += 1
    assert stream.final()["num_generated"] == 3
    # a turn reads one step: the prefill and two decode steps
    assert turns == 3 == sum(eng.stats()["steps"].values())
    assert not eng.step()
    _assert_drained(eng)


@pytest.mark.parametrize("lengths", [(7, 5), (5, 7)],
                         ids=["longest-first", "longest-last"])
@pytest.mark.parametrize("model", MODELS)
def test_a_decode_lane_takes_its_unread_id_from_the_device(model, lengths):
    """Runner level: a lane fed `token=-1` reads what the program before
    it left at its slot, and computes what the host's id computes. The
    decode program runs its lanes longest first (PR 33), and `collect`
    hands lane i's id and logits back to lane i whichever way the caller
    had them, with the program before still unread."""
    from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters

    fam = model if model != "olmoe" else "llama"
    adapter = adapters()[fam]
    cfg = adapter.presets["olmoe_tiny" if model == "olmoe" else "tiny"]()
    if model not in ("nemotron_h", "mimo_v2"):  # tiny presets float32 as they are
        cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    params = adapter.init_fn(jax.random.PRNGKey(0), cfg)

    def runner():
        return ModelRunner(adapter, cfg, params, block_size=4,
                           num_blocks=16, max_model_len=32,
                           max_batch_size=4, prefill_chunk_size=8)

    tables = [[3, 7, 2, 9], [5, 1, 8, 4]]
    prompts = _prompts(lengths, seed=2)

    def run(on_device):
        r = runner()
        first = [r.collect(r.launch_prefill(p, t, 0.0, slot=s))[0]
                 for p, t, s in zip(prompts, tables, (2, 0))]
        toks, rows = list(first), []
        for step in range(3):
            items = [DecodeItem(-1 if on_device else toks[i],
                                len(prompts[i]) + step, tables[i], 0.0,
                                slot=(2, 0)[i]) for i in range(2)]
            toks, logits = r.decode(items)
            rows.append(logits)
        assert np.asarray(r.slot_tokens)[[2, 0]].tolist() == toks
        # a step in flight: two programs launched, then read in turn, give
        # each lane what one program at a time, fed by the host, gives it
        def items(step, fed):
            return [DecodeItem(fed[i], len(prompts[i]) + step, tables[i],
                               0.0, slot=(2, 0)[i]) for i in range(2)]

        if on_device:
            ahead = r.launch_decode(items(3, [-1, -1]))
            behind = r.launch_decode(items(4, [-1, -1]))
            (ids, _), (ids2, _) = r.collect(ahead), r.collect(behind)
        else:
            ids, _ = r.decode(items(3, toks))
            ids2, _ = r.decode(items(4, ids))
        assert np.asarray(r.slot_tokens)[[2, 0]].tolist() == ids2
        return first, toks + ids + ids2, np.stack(rows), r

    a, b = run(True), run(False)
    assert a[:2] == b[:2]
    np.testing.assert_array_equal(a[2], b[2])
    # no program beyond the buckets' own
    assert a[3].compiled_signatures() == b[3].compiled_signatures() == 2
