"""The serve engine keeps each count once: `LLMEngine.stats()` is the
account, and a counter or a gauge of the metrics page is that number read
when the page is asked for. One case a series: page value == the number
in `stats()` it is read from, after prefill, chunks, decode, a preemption,
an abort and a weight install, on a dense family, on one with experts and
recurrent state, and on an engine that speculates."""

import dataclasses
import gc
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.util.metrics import prometheus_text
from ray_tpu.util.watchtower import parse_prometheus


def _by_kind(nested):  # {kind: {what: n}} -> {(kind, what): n}
    return {(kind, what): n for kind, by in nested.items()
            for what, n in by.items()}


def _kv(key):
    return lambda st: {(kind,): pool[key] for kind, pool in st["kv"].items()}


def _moe(key):
    return lambda st: {(kind,): acc[key] for kind, acc in st["moe"].items()}


def _state(key):
    return lambda st: {(): st["state"].get(key, 0)}


def _spec(pick):
    return lambda st: {(): pick(st)}


# series -> what it shows of `stats()`: {tag values after the model's: n}
SERIES = {
    "serve_llm_tokens_generated_total":
        lambda st: {(): st["tokens_generated"]},
    "serve_llm_requests_total": lambda st: {
        (outcome,): n for outcome, n in st["finished_by_outcome"].items()},
    "serve_llm_preemptions_total": lambda st: {(): st["preemptions"]},
    "serve_llm_queue_depth": lambda st: {(): st["waiting"]},
    "serve_llm_running": lambda st: {(): st["running"]},
    "serve_llm_cache_utilization":
        lambda st: {(): st["cache_utilization"]},
    "serve_llm_prefix_cache_hits_total":
        lambda st: {(): st["prefix_hit_pages"]},
    "serve_llm_prefix_cache_misses_total":
        lambda st: {(): st["prefix_miss_pages"]},
    "serve_llm_prefix_cache_evictions_total":
        lambda st: {(): st["prefix_evictions"]},
    "serve_llm_prefix_cached_blocks": lambda st: {(): st["blocks_cached"]},
    "serve_llm_prefill_chunks_total": lambda st: {(): st["prefill_chunks"]},
    "serve_llm_weight_swaps_total":
        lambda st: {(): st["weights"]["installs"] - 1},
    "serve_llm_spec_proposed_total": _spec(lambda st: st["spec_proposed"]),
    "serve_llm_spec_accepted_total": _spec(lambda st: st["spec_accepted"]),
    "serve_llm_spec_rejected_total":
        _spec(lambda st: st["spec_proposed"] - st["spec_accepted"]),
    "serve_llm_spec_accept_ratio":
        _spec(lambda st: st["spec_accepted"] / max(1, st["spec_proposed"])),
    "serve_llm_weight_bytes":
        lambda st: {(): st["weights"]["resident_bytes"]},
    "serve_llm_weight_cast_leaves":
        lambda st: {(): st["weights"]["cast_leaves"]},
    "serve_llm_d2h_bytes_total":
        lambda st: {(kind,): n for kind, n in st["d2h_bytes"].items()},
    "serve_llm_ctx_slots_total": lambda st: _by_kind(st["context"]),
    "serve_llm_moe_pairs_total": _moe("pairs"),
    "serve_llm_moe_experts_touched_total": _moe("experts_touched"),
    "serve_llm_moe_layer_calls_total": _moe("layer_calls"),
    "serve_llm_moe_load_imbalance": lambda st: {
        (kind,): max(acc["expert_pairs"]) / np.mean(acc["expert_pairs"])
        for kind, acc in st["moe"].items()},
    "serve_llm_steps_launched_total": lambda st: {
        (kind, ahead): n for ahead, which in
        (("1", "launched_ahead"), ("0", "launched_drained"))
        for kind, n in st["overlap"][which].items()},
    "serve_llm_step_drains_total": lambda st: {
        (reason,): n for reason, n in st["overlap"]["drains"].items()},
    "serve_llm_discarded_tokens_total":
        lambda st: {(): st["overlap"]["discarded_tokens"]},
    "serve_llm_state_bytes": _state("bytes"),
    "serve_llm_state_resets_total": _state("resets"),
    "serve_llm_state_carried_total": _state("carried"),
    "serve_llm_state_decode_lanes_total": _state("decode_lanes"),
    "serve_llm_kv_pages_used": _kv("pages_used"),
    "serve_llm_kv_pages_free": _kv("pages_free"),
    "serve_llm_kv_largest_table": _kv("largest_table"),
    "serve_llm_kv_released_total": _kv("released_behind_window"),
    "serve_llm_kv_prefix_total": lambda st: {
        (outcome,): sum(pool["prefix_" + outcome]
                        for pool in st["kv"].values())
        for outcome in ("taken", "declined")},
    "serve_llm_kv_rows_written_total": lambda st: {
        (kind, path): pool["rows_written_" + path]
        for kind, pool in st["kv"].items()
        for path in ("paged", "rowwise")},
}
HISTOGRAMS = {
    "serve_llm_step_ms", "serve_llm_ttft_ms", "serve_llm_itl_ms",
    "serve_llm_verify_step_ms", "serve_slo_ttft_ms", "serve_slo_tpot_ms",
    "rl_weight_swap_seconds"}
SCENARIOS = ("gpt2", "lfm2", "gpt2-speculating")


def _engine(scenario, **overrides):
    from ray_tpu.models import gpt2

    kw = dict(block_size=4, num_blocks=11, max_model_len=32,
              max_batch_size=4, prefill_chunk_size=8, seed=0)
    if scenario == "lfm2":
        kw.update(model="lfm2", preset="tiny")
    else:
        kw.update(model="gpt2", model_config=dataclasses.replace(
            gpt2.GPT2Config.tiny(), dtype=jnp.float32, remat=False))
    if scenario == "gpt2-speculating":
        kw.update(num_blocks=64,
                  speculative={"method": "ngram", "num_draft_tokens": 2})
    kw.update(overrides)
    return LLMEngine(EngineConfig(**kw))


def _drive(engine, streams):
    turns = 0
    while any(s.final() is None for s in streams):
        engine.step()
        turns += 1
        assert turns < 3000


def _work(engine, scenario):
    """Two prompts of two chunks each in a pool that cannot hold both to
    their ends (one is preempted and recomputed), then a request aborted
    while it decodes, then a weight install."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 200, size=n).tolist() for n in (10, 11)]
    if scenario == "gpt2-speculating":  # runs an n-gram proposer repeats
        prompts = [[5, 6, 7] * 4, [9, 8] * 5]
    sp = SamplingParams(max_tokens=12)
    _drive(engine, [engine.add_request(p, sp) for p in prompts])
    doomed = engine.add_request(prompts[0], sp)
    for _ in range(4):
        engine.step()
    engine.abort_request(doomed)
    engine.update_weights(1, engine.runner.params)


def _page(model, of=None):
    """{series: {tag values after the model's: value}} of `model`'s
    counters and gauges; `of`: as if those were the engines alive."""
    with pytest.MonkeyPatch.context() as mp:
        if of is not None:
            mp.setattr(engine_mod, "engines", lambda: list(of))
        page = parse_prometheus(prometheus_text())
    out: dict = {}
    for (name, tags), value in page.items():
        tags = dict(tags)
        if name in SERIES and tags.pop("model", None) == model:
            out.setdefault(name, {})[tuple(tags.values())] = value
    return out


def _tag_order(name):
    """parse_prometheus sorts a series' tags by key: the order `SERIES`
    gives them in (the metric's `tag_keys`) -> that one."""
    from ray_tpu.util.metrics import _registry

    keys = [k for k in _registry._metrics[name].tag_keys if k != "model"]
    return sorted(range(len(keys)), key=lambda i: keys[i])


@pytest.fixture(scope="module")
def accounts():
    """{scenario: (stats(), the page with the scenario's engine the only
    one alive less what the engines gone before it had left there)}."""
    out = {}
    for scenario in SCENARIOS:
        gc.collect()
        engine = _engine(scenario)
        model = engine.config.model
        left = _page(model, of=[])
        _work(engine, scenario)
        shown = _page(model, of=[engine])
        for name, series in shown.items():
            for key in series:
                series[key] -= left.get(name, {}).get(key, 0.0)
        out[scenario] = (engine.stats(), shown)
    return out


@pytest.mark.parametrize("name", sorted(SERIES))
def test_the_page_shows_what_stats_returns(accounts, name):
    order = _tag_order(name)
    for scenario, (stats, shown) in accounts.items():
        want = {tuple(key[i] for i in order): float(n)
                for key, n in SERIES[name](stats).items()}
        got = {key: n for key, n in shown.get(name, {}).items()
               if n or key in want}  # the gone engines' zeros are no one's
        assert got == pytest.approx(want), (scenario, name)


def test_the_work_moved_every_kind_of_count(accounts):
    """The scenarios are worth comparing: each thing the issue names
    happened in them."""
    stats, _ = accounts["gpt2"]
    assert stats["preemptions"] > 0 and stats["prefill_chunks"] >= 4
    assert stats["finished_by_outcome"]["aborted"] == 1
    assert stats["finished_by_outcome"]["length"] == 2
    assert stats["tokens_generated"] > 24  # the recompute's and the doomed
    assert stats["weights"]["installs"] == 2
    assert stats["overlap"]["drains"]["swap"] + \
        stats["overlap"]["drains"]["abort"] >= 1
    stats, _ = accounts["lfm2"]
    assert stats["preemptions"] > 0 and stats["state"]["carried"] > 0
    assert stats["moe"]["decode"]["pairs"] > 0
    stats, _ = accounts["gpt2-speculating"]
    assert stats["spec_proposed"] > stats["spec_accepted"] > 0


def test_every_series_of_the_engine_is_a_view_or_a_histogram():
    """The engine declares 44 series: 37 read at scrape time, and the
    seven histograms, observed where they happen."""
    from ray_tpu.util.metrics import _registry

    _engine("gpt2", num_blocks=64)
    mine = {name: m for name, m in _registry._metrics.items()
            if name in SERIES or name in HISTOGRAMS}
    assert len(mine) == 44 and len(SERIES) == 37
    for name, metric in mine.items():
        assert (metric.collect is None) == (name in HISTOGRAMS), name
        assert (metric.TYPE == "histogram") == (name in HISTOGRAMS), name


def _counters(model):
    return {(name, key): n for name, series in _page(model).items()
            for key, n in series.items() if name.endswith("_total")}


def test_a_counter_stays_where_it_was_when_its_engine_is_collected():
    gc.collect()
    before = _counters("gpt2")
    engine = _engine("gpt2")
    _work(engine, "gpt2")
    with_it = _counters("gpt2")
    moved = {k for k, n in with_it.items() if n != before.get(k, 0.0)}
    assert len({name for name, _ in moved}) >= 12
    gone = weakref.ref(engine)
    del engine
    gc.collect()
    assert gone() is None
    assert _counters("gpt2") == with_it


def test_two_engines_of_one_model_add():
    gc.collect()
    before = _page("lfm2")
    pair = [_engine("lfm2"), _engine("lfm2", num_blocks=24)]
    for engine in pair:
        _work(engine, "lfm2")
    after = _page("lfm2")
    stats = [engine.stats() for engine in pair]
    for name in ("serve_llm_tokens_generated_total",
                 "serve_llm_kv_rows_written_total",
                 "serve_llm_moe_pairs_total", "serve_llm_state_bytes",
                 "serve_llm_kv_pages_free", "serve_llm_weight_bytes"):
        order = _tag_order(name)
        for key, n in after[name].items():
            want = sum(SERIES[name](st)[tuple(
                key[order.index(i)] for i in range(len(key)))]
                for st in stats)
            assert n - before.get(name, {}).get(key, 0.0) == want, name
    # a ratio is taken of the added parts, not added
    used = sum(st["blocks_used"] for st in stats)
    total = sum(st["blocks_total"] for st in stats)
    others = [e for e in engine_mod.engines() if e.config.model == "lfm2"
              and e not in pair]
    if not others:
        assert after["serve_llm_cache_utilization"][()] \
            == pytest.approx(used / total)
