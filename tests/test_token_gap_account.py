"""The token gap, accounted from inside (ISSUE 36): every gap between two
streamed tokens of one request is taken where the loop emits the second and
put down to what the loop did in it; the turn's phases hold the turn; a
stream times its own half of the hand-off to the client; five readers of
the benchmark take a window's deltas of all three.

No case asserts on real elapsed time. Where a duration matters,
`time.perf_counter` is a counter that advances by one a call."""

import dataclasses
import importlib.util
import itertools
import os
import time

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
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm.engine import (
    GAP_CAUSES,
    GAP_EDGES_MS,
    ITL_BOUNDS_MS,
    RequestStream,
    StreamAccount,
    gap_bucket,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(**overrides):
    from ray_tpu.models import gpt2

    kw = dict(block_size=4, num_blocks=96, max_model_len=48,
              max_batch_size=4, prefill_chunk_size=8, seed=0,
              model="gpt2", model_config=dataclasses.replace(
                  gpt2.GPT2Config.tiny(), dtype=jnp.float32, remat=False))
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 60, size=n).tolist()


def _drive(engine, streams=()):
    turns = 0
    while engine.step():
        turns += 1
        assert turns < 2000, "engine made no progress"
    assert all(s.final() is not None for s in streams)
    return turns


def _gaps(engine):
    """{cause: gaps counted} of the engine so far."""
    by_cause = engine.stats()["token_gaps"]["by_cause"]
    return {cause: sum(counts) for cause, counts in by_cause.items()}


def _rose(engine, before):
    return {c: n - before[c] for c, n in _gaps(engine).items() if
            n != before[c]}


def _assert_identity(engine, finals):
    """Every token is its request's first, one of a verify dispatch's
    run, or has a gap."""
    gaps = engine.stats()["token_gaps"]
    emitted = sum(f["num_generated"] for f in finals)
    started = sum(f["num_generated"] > 0 for f in finals)
    assert sum(_gaps(engine).values()) + gaps["burst"] + started == emitted
    for cause in GAP_CAUSES:
        n = sum(gaps["by_cause"][cause])
        assert (gaps["sum_ms"][cause] > 0) == (n > 0)
        assert gaps["max_ms"][cause] * n >= gaps["sum_ms"][cause]


# ---------------------------------------------------------------------------
# the edges
# ---------------------------------------------------------------------------

def test_edges_are_fine_to_20_ms_then_double():
    assert len(GAP_EDGES_MS) == 210
    assert GAP_EDGES_MS[:3] == (0.1, 0.2, 0.3)
    assert GAP_EDGES_MS[199] == 20.0 and GAP_EDGES_MS[-1] == 20480.0
    assert list(GAP_EDGES_MS) == sorted(set(GAP_EDGES_MS))
    # bucket i holds [edges[i-1], edges[i]); one more above the last
    assert [gap_bucket(ms) for ms in (0.0, 0.05, 0.1, 5.53, 19.99, 20.0,
                                      39.9, 40.0, 20480.0, 1e9)] \
        == [0, 0, 1, 55, 199, 200, 200, 201, 210, 210]
    # the operator's boundaries are the catalogued family's
    assert ITL_BOUNDS_MS[0] == 0.5 and ITL_BOUNDS_MS[-1] == 1000


# ---------------------------------------------------------------------------
# (1) every gap, by cause
# ---------------------------------------------------------------------------

def test_two_decode_steps_back_to_back_are_a_decode_gap():
    eng = _engine()
    s = eng.add_request(_prompt(6), SamplingParams(max_tokens=7))
    _drive(eng, [s])
    # one chunk, then six decode steps, each launched behind the read of
    # the step before it
    assert _gaps(eng) == {"after_preempt": 0, "after_prefill": 0,
                          "after_drain": 0, "decode": 6}
    _assert_identity(eng, [s.final()])


def test_a_newcomers_chunk_read_between_two_tokens_is_after_prefill():
    eng = _engine()
    a = eng.add_request(_prompt(6), SamplingParams(max_tokens=12))
    for _ in range(4):
        eng.step()
    assert len(a._q.queue) >= 2  # `a` is decoding
    before = _gaps(eng)
    # three chunks of 8: `a` waits each out between two of its tokens
    b = eng.add_request(_prompt(20, seed=1), SamplingParams(max_tokens=2))
    _drive(eng, [a, b])
    rose = _rose(eng, before)
    assert rose["after_prefill"] == 3
    # `b`'s own second token follows its last chunk with no prefill step
    # read between: a plain decode gap, as `a`'s others
    assert set(rose) == {"after_prefill", "decode"}
    _assert_identity(eng, [a.final(), b.final()])


def test_the_token_behind_a_swaps_drain_is_after_drain():
    from ray_tpu.models import gpt2

    eng = _engine()
    s = eng.add_request(_prompt(6), SamplingParams(max_tokens=10))
    for _ in range(4):
        eng.step()
    before = _gaps(eng)
    eng.update_weights(1, gpt2.init_gpt2(jax.random.PRNGKey(7),
                                         eng.model_cfg))
    assert eng.stats()["overlap"]["drains"]["swap"] == 1
    # the step the swap read had been launched ahead: a decode gap
    assert _rose(eng, before) == {"decode": 1}
    _drive(eng, [s])
    # the next was planned with nothing in flight, the rest ahead again
    rose = _rose(eng, before)
    assert rose["after_drain"] == 1
    assert rose["decode"] == 10 - 1 - sum(before.values()) - 1
    _assert_identity(eng, [s.final()])


def test_a_recomputed_request_comes_back_after_preempt():
    eng = _engine(num_blocks=14, max_model_len=32,
                  enable_prefix_cache=False)
    streams = [eng.add_request(_prompt(n, seed=i),
                               SamplingParams(max_tokens=14))
               for i, n in enumerate((9, 10, 8))]
    _drive(eng, streams)
    finals = [s.final() for s in streams]
    preempted = sum(f["preemptions"] for f in finals)
    assert preempted > 0
    # one gap a preemption: the token its recompute's last chunk samples
    # (a request preempted twice before that token has one for both)
    assert 0 < _gaps(eng)["after_preempt"] <= preempted
    _assert_identity(eng, finals)


def test_a_verify_dispatchs_run_is_a_burst_behind_one_gap():
    eng = _engine(speculative=SpeculativeConfig(num_draft_tokens=3))
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6], [9, 9, 9, 9, 9]]
    streams = [eng.add_request(p, SamplingParams(max_tokens=10))
               for p in prompts]
    _drive(eng, streams)
    st = eng.stats()
    assert st["spec_accepted"] > 0
    gaps = st["token_gaps"]
    # the accepted drafts a dispatch committed beyond its first token
    assert 0 < gaps["burst"] <= st["spec_accepted"]
    # a proposer reads what a step commits: nothing is ever launched ahead
    assert _gaps(eng)["decode"] == 0 and _gaps(eng)["after_drain"] > 0
    _assert_identity(eng, [s.final() for s in streams])


def test_the_gaps_reach_the_operators_family_once_a_step():
    eng = _engine()
    s = eng.add_request(_prompt(6), SamplingParams(max_tokens=5))
    _drive(eng, [s])
    page = "\n".join(eng._m_itl.expose())
    line = [ln for ln in page.splitlines()
            if ln.startswith("serve_llm_itl_ms_count")
            and 'cause="decode"' in ln]
    assert line and float(line[0].rsplit(" ", 1)[1]) >= 4
    assert 'le="0.5"' in page and 'le="1000"' in page
    # nothing is left waiting for the next step's bookkeeping
    assert eng._itl_pending == {}


# ---------------------------------------------------------------------------
# (2) the turn's account
# ---------------------------------------------------------------------------

class _Ticks:
    """`time.perf_counter` as a counter that advances by one a call."""

    def __init__(self):
        self.calls = itertools.count(1)

    def __call__(self):
        return float(next(self.calls))


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_no_clock_reading_of_a_turn_lies_outside_a_phase(monkeypatch, spec):
    """With a clock that ticks once a reading, `wall - sum(phases)` counts
    the readings that fall between two phases. A phase takes two, which
    leave one tick outside it; step() takes one as it is entered, a turn
    one as it ends, `stats()` one for the wall so far. If the remainder
    is just that, every other reading (a step's planning,
    its bookkeeping, a token's emission) lies inside a phase: the account
    is closed but for what runs between two phases."""
    eng = _engine(speculative=SpeculativeConfig(num_draft_tokens=3)
                  if spec else None)
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6], _prompt(19, seed=2)]

    def serve():
        streams = [eng.add_request(p, SamplingParams(max_tokens=8))
                   for p in prompts]
        calls = 0
        while True:
            calls += 1
            if not eng.step():
                break
        assert all(s.final() is not None for s in streams)
        return calls

    serve()  # every program compiled, every metric's first write done
    entered = []
    phase = eng.phases.phase
    monkeypatch.setattr(
        eng.phases, "phase",
        lambda name: entered.append(name) or phase(name))
    monkeypatch.setattr(time, "perf_counter", _Ticks())
    eng._loop_t0 = None  # the loop's clock starts with the ticking one
    before = eng.stats()
    calls = serve()
    after = eng.stats()
    monkeypatch.undo()
    spent = sum(after["step_phase_seconds"].values()) \
        - sum(before["step_phase_seconds"].values())
    wall = after["loop"]["wall_s"]
    turns = sum(after["steps"][k] - before["steps"][k]
                for k in ("decode", "prefill"))
    assert wall - spent == calls + turns + len(entered)
    assert {"schedule", "prepare", "dispatch", "fetch", "commit", "emit",
            "bookkeep", "release"} <= set(entered)
    assert "yield" not in entered  # nobody else wanted the engine
    # the turns, by the kind of step read, are the steps; a turn is inside
    # the loop's wall
    by_kind = after["loop"]["turns"]
    for kind in ("decode", "prefill"):
        d = by_kind[kind]["count"] - before["loop"]["turns"][kind]["count"]
        assert d == after["steps"][kind] - before["steps"][kind] > 0
        assert sum(by_kind[kind]["hist"]) == by_kind[kind]["count"]
        assert by_kind[kind]["over_250ms_s"] >= 0
    assert sum(t["wall_s"] - before["loop"]["turns"][k]["wall_s"]
               for k, t in by_kind.items()) < wall


def test_the_loop_yields_to_a_swap_under_its_own_phase():
    import threading

    from ray_tpu.models import gpt2

    eng = _engine()
    s = eng.add_request(_prompt(6), SamplingParams(max_tokens=30))
    done = threading.Event()

    def loop():
        while not done.is_set():
            eng.step()

    th = threading.Thread(target=loop, daemon=True)
    th.start()
    try:
        params = gpt2.init_gpt2(jax.random.PRNGKey(7), eng.model_cfg)
        for v in range(1, 6):
            eng.update_weights(v, params)
        assert s.next_event(timeout=60) is not None
    finally:
        done.set()
        th.join(timeout=60)
    assert not th.is_alive()
    assert eng.stats()["step_phase_seconds"]["yield"] > 0


def test_warmup_leaves_the_phases_at_zero():
    eng = _engine(model_config=None, preset="tiny", block_size=8,
                  num_blocks=16, max_model_len=16, max_batch_size=1,
                  prefill_chunk_size=8)
    eng.warmup()
    st = eng.stats()
    assert set(st["step_phase_seconds"].values()) == {0.0}
    assert st["loop"]["wall_s"] == 0.0
    assert st["startup_seconds"]["warmup"] > 0


# ---------------------------------------------------------------------------
# (3) the replica's half of the stream
# ---------------------------------------------------------------------------

def test_pickup_and_ship_of_a_stream_driven_by_hand(monkeypatch):
    clock = iter([
        10.0,          # token 0 put down
        10.004,        # token 0 taken up: pickup 4 ms
        10.0045,       # asked for the next: ship 0.5 ms
        10.030,        # token 1 put down
        10.031,        # taken up: pickup 1 ms
        10.033,        # asked for the next: ship 2 ms; the end
    ])
    monkeypatch.setattr(time, "perf_counter", lambda: next(clock))
    account = StreamAccount()
    stream = RequestStream(7, account)
    stream._emit({"token": 11, "index": 0})
    assert stream.next_event(timeout=1) == {"token": 11, "index": 0}
    assert stream._q.empty() and account.stats()["items"] == 0
    with pytest.raises(TimeoutError):
        stream.next_event(timeout=0.01)  # asked: token 0 is shipped
    stream._emit({"token": 12, "index": 1})
    assert next(stream) == {"token": 12, "index": 1}
    stream._close({"done": True})
    with pytest.raises(StopIteration):
        next(stream)
    assert stream.next_event() is None  # persistently, and no new reading
    got = account.stats()
    assert got["items"] == 2
    assert got["pickup_s"] == pytest.approx(0.005)
    assert got["ship_s"] == pytest.approx(0.0025)
    assert got["max_ms"] == pytest.approx(4.5)
    assert got["edges_ms"] == list(GAP_EDGES_MS)
    # hand-offs of 4.5 ms and of 3 ms
    assert {i: n for i, n in enumerate(got["handoff"]) if n} \
        in ({45: 1, 30: 1}, {44: 1, 30: 1}, {45: 1, 29: 1}, {44: 1, 29: 1})


def test_a_stream_folds_every_64_items_and_the_event_is_unchanged():
    account = StreamAccount()
    stream = RequestStream(1, account)
    for i in range(70):
        stream._emit({"token": i, "index": i})
    stream._close({"done": True})
    seen = []
    for ev in stream:
        seen.append(ev)
        # the time an event was put down travels beside it, not in it
        assert set(ev) == {"token", "index"}
        if len(seen) == 66:
            assert account.stats()["items"] == 64
    assert [e["token"] for e in seen] == list(range(70))
    assert account.stats()["items"] == 70
    assert sum(account.stats()["handoff"]) == 70
    # an engine's streams fold into its own account
    eng = _engine()
    s = eng.add_request(_prompt(5), SamplingParams(max_tokens=4))
    _drive(eng, [s])
    assert [e["index"] for e in s] == [0, 1, 2, 3]
    assert eng.stats()["stream"]["items"] == 4


def test_streams_on_many_threads_fold_into_one_account_without_loss():
    """More request threads than cores, each through a stream of its
    own, all folding into the engine's one account: no item is lost."""
    import sys
    import threading

    account = StreamAccount()
    threads, per_stream = 4 * (os.cpu_count() or 4), 300

    def consume():
        stream = RequestStream(0, account)
        for i in range(per_stream):
            stream._emit({"token": i, "index": i})
        stream._close({"done": True})
        assert sum(1 for _ in stream) == per_stream

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=consume, daemon=True)
                   for _ in range(threads)]
        for th in workers:
            th.start()
        for th in workers:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in workers)
    got = account.stats()
    assert got["items"] == sum(got["handoff"]) == threads * per_stream
    assert got["pickup_s"] > 0 and got["ship_s"] > 0


# ---------------------------------------------------------------------------
# (4) the five readers
# ---------------------------------------------------------------------------

def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(gaps=None, turns=None, handoff=None, wall=0.0, phases=None,
           items=0):
    """An `engine_stats()` with counts at the given {bucket: n}."""
    def counts(at):
        out = [0] * (len(GAP_EDGES_MS) + 1)
        for i, n in (at or {}).items():
            out[i] = n
        return out

    edges = list(GAP_EDGES_MS)
    return {
        "token_gaps": {
            "edges_ms": edges,
            "by_cause": {c: counts((gaps or {}).get(c)) for c in GAP_CAUSES},
            "sum_ms": dict.fromkeys(GAP_CAUSES, 0.0),
            "max_ms": dict.fromkeys(GAP_CAUSES, 0.0), "burst": 0},
        "loop": {"wall_s": wall, "turns": {
            k: {"count": sum((turns or {}).get(k, {}).values()),
                "wall_s": 0.0, "max_ms": 0.0,
                "hist": counts((turns or {}).get(k)), "over_250ms_s": 0.0}
            for k in ("decode", "prefill")}},
        "stream": {"items": items, "pickup_s": 0.0, "ship_s": 0.0,
                   "edges_ms": edges, "handoff": counts(handoff),
                   "max_ms": 0.0},
        "step_phase_seconds": dict(phases or {"fetch": 0.0}),
        "preemptions": 0,
    }


def _observed(before, after):
    return {"before": {"stats": before, "page": ""},
            "after": {"stats": after, "page": ""}}


_ZERO = _stats()
# 100 gaps in the bucket [5.5, 5.6): p95 lies 95% of the way through it
_ONE_BUCKET = _stats(
    gaps={"decode": {55: 100}}, turns={"decode": {34: 100}},
    handoff={2: 100}, items=100, wall=1.0, phases={"fetch": 0.999})
# 90 decode gaps at 3.4-3.5 ms; above them 4 decode and 6 after_prefill at
# 9.0-9.1 ms: p95 is in the upper bucket, six tenths of which are the chunk's
_SPLIT_TAIL = _stats(
    gaps={"decode": {34: 90, 90: 4}, "after_prefill": {90: 6}},
    turns={"decode": {34: 94}, "prefill": {90: 5, 202: 1}},
    handoff={1: 60, 3: 40}, items=100, wall=2.0, phases={"fetch": 1.9})

READINGS = {
    "engine_itl_p95_ms": (5.595, 9.05),
    "itl_tail_after_prefill_pct": (0.0, 60.0),
    "stream_handoff_p95_ms": (0.295, 0.3875),
    "turn_unphased_ms": (0.01, 1.0),
    "turn_max_ms": (3.5, 160.0),
}


@pytest.mark.parametrize("name", sorted(READINGS))
@pytest.mark.parametrize("window", ["empty", "no_counters", "one_bucket",
                                    "split_tail"])
def test_a_reader_on_a_synthetic_window(name, window, capsys):
    read = _reader(name)
    if window == "empty":  # nothing happened between the two snapshots
        assert read(_observed(_ONE_BUCKET, _ONE_BUCKET)) is None
        assert read({}) is None
    elif window == "no_counters":  # a program older than the counters
        old = {"step_phase_seconds": {"fetch": 1.0}, "preemptions": 0}
        assert read(_observed(old, old)) is None
    else:
        after = _ONE_BUCKET if window == "one_bucket" else _SPLIT_TAIL
        want = READINGS[name][window == "split_tail"]
        assert read(_observed(_ZERO, after)) == pytest.approx(want)
    capsys.readouterr()


def test_the_new_metrics_are_listed_for_the_open_loop_cells_alone():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in READINGS]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(mine[0]["name"])  # appended together, in this order
    assert names[at:at + 5] == [m["name"] for m in mine] and len(mine) == 5
    for m in mine:
        assert m["moves"] == "itl_p95_ms" and m["better"] == "lower"
        assert m["workloads"] == ["serve-gpt2-large-chat-steady",
                                  "serve-gpt2-large-long-decode"]
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))


# ---------------------------------------------------------------------------
# the swap of metric families
# ---------------------------------------------------------------------------

def test_one_family_in_two_out_and_the_catalog_follows():
    from ray_tpu.util.metrics_catalog import CATALOG, source_metrics

    src = source_metrics()
    cat = {m["name"]: m["type"] for m in CATALOG}
    assert src["serve_llm_itl_ms"] == cat["serve_llm_itl_ms"] == "histogram"
    for gone in ("serve_llm_prefill_stall_ms", "serve_llm_tokens_per_sec"):
        assert gone not in src and gone not in cat
    assert set(src) == set(cat)
    eng = _engine()
    assert not hasattr(eng, "_tokens_window")
    assert engine_mod.SLOW_TURN_MS == 250.0
