"""Rehearsal of `serve-xing4.0-29b-a4b-long-doc-sat` on the CPU at `tiny`:
the cell's files load and say what ISSUE 51 asked, the generator draws the
sizes the traffic file states, and the xing4 family goes through
`serve.run()` and the serve kind's own runner with the reference and the
layer parity as the configuration names them (at the tiny preset's
widths). Shows control flow, counts and the correctness check; no number
from here is a metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cell_xing4_cpu.py -q -p no:cacheprovider
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

# the one runtime a module (tests/test_benchmark_selftests.py collects this
# file beside that one: the same fixture object)
from benchmark.selftest.test_cell_granite_hybrid_cpu import (  # noqa: E402,F401
    cluster,
)

CELL = "serve-xing4.0-29b-a4b-long-doc-sat"
# the dense latent kind's cell: the family at `tiny`, prompts of 24 to 72
# tokens over pages of 8
CONFIG = {
    "n_embd": 64, "n_layer": 3, "n_head": 4, "n_positions": 128,
    "vocab_size": 512,
    "model": {"family": "xing4", "preset": "tiny",
              "config": "ray_tpu.models.xing4:Xing4Config.tiny",
              "init": "ray_tpu.models.xing4:init_xing4",
              "reference": "benchmark.selftest.tiny_xing4:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 129, "max_batch_size": 4,
               "max_model_len": 128, "prefill_chunk_size": 32},
    "deployment": {"max_ongoing_requests": 8, "num_replicas": 1},
    "logprob_tolerance": 0.001,
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_was_asked():
    from benchmark import reference_xing4, run
    from benchmark.parity_mimo_v2 import program_config

    bench = _bench()
    cell, config, traffic = run.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "long-doc-sat")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced_keys"]
    assert len(config["reduced"]) == len(entry["reduced"]) == 5
    # every number of the catalog row's config under its own key, but
    # for the five that were cut
    for key, value in config["published"].items():
        assert (config[key] == value) != (key in entry["reduced"]), key
    cfg = program_config(config)
    assert cfg.n_params() == config["parameters"]
    assert cfg.hc_sinkhorn_iters == 20 and cfg.hc_mult == 4
    engine = config["engine"]
    assert (engine["max_batch_size"], engine["block_size"],
            engine["prefill_chunk_size"]) == (32, 16, 256)
    assert engine["max_model_len"] == config["max_position_embeddings"] \
        == traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert engine["enable_prefix_cache"]
    assert config["deployment"]["max_ongoing_requests"] == 64
    arch = reference_xing4.published_arch()
    assert reference_xing4.score_scale(arch) == pytest.approx(
        cfg.softmax_scale)
    assert set(config["layer_parity"]["limits"]) == {
        "mhc_coef", "mhc_mix", "mixer", "decode_mixer", "ffn_dense",
        "ffn_experts", "routing"}
    assert config["layer_parity"]["rows"] \
        - config["layer_parity"]["decode_rows"] >= 8192
    # the cell is on the lists of the metrics it reports
    on = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
          if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "setup_s", "mhc_share_pct",
            "mhc_roofline_pct", "mla_dense_share_pct",
            "mla_dense_roofline_pct", "mla_ctx_slots_per_row",
            "batch_occupancy", "moe_held_pairs_share_pct"} <= on
    assert not {"mla_attn_share_pct", "dsa_index_share_pct",
                "ctx_kernel_steps_pct"} & on


def test_the_generator_draws_the_sizes_the_traffic_states():
    from benchmark import run, traffic_gen

    _, config, traffic = run.load_cell(_bench(), CELL)
    assert (traffic["loop"], traffic["clients"],
            traffic["cycle_requests"]) == ("closed", 64, 32)
    pool = traffic_gen.ClosedPool(traffic, 2**31 + 5, config["vocab_size"])
    spec = traffic["prompt_len"]
    assert spec["min"] <= pool.plens.min() and pool.plens.max() <= spec["max"]
    # the stratified draw: the median near the stated one, the mean 13%
    # above it (sigma 0.5), the same multiset for every seed
    assert 0.93 < np.median(pool.plens) / spec["median"] < 1.07
    assert 1.05 < pool.plens.mean() / spec["median"] < 1.25
    other = traffic_gen.ClosedPool(traffic, 77, config["vocab_size"])
    assert sorted(other.plens) == sorted(pool.plens)
    assert 300 < pool.olens.mean() < 340
    # the longest request fits the engine's lanes
    assert pool.plens.max() + pool.olens.max() \
        <= config["engine"]["max_model_len"]
    req = pool.get(3)
    assert len(req.prompt) == pool.plens[3] and max(req.prompt) \
        < config["vocab_size"]


def test_serve_cell_of_the_dense_latent_kind(cluster, monkeypatch):
    """Closed loop through `serve.run()`: the log-probs against the plain
    reference AND the layer parity at tiny, the kind's counters in every
    poll of the window, and the counter-based reader."""
    from benchmark.kinds import serve as serve_kind
    from benchmark.run import read_layer_metric
    from ray_tpu import serve

    monkeypatch.setattr(serve_kind, "CHECK_PROMPT_LENS", (5, 20, 40, 70))
    monkeypatch.setattr(serve_kind, "CHECK_MAX_TOKENS", 4)
    monkeypatch.setattr(serve_kind, "TRACE_FOR_S", 1.0)
    traffic = {"kind": "serve", "loop": "closed", "base_seed": 3,
               "clients": 6, "preroll_s": 0.5, "cycle_requests": 8,
               "prompt_len": {"dist": "uniform", "min": 24, "max": 72},
               "output_len": {"dist": "uniform", "min": 4, "max": 12}}
    cell = {"name": "selftest-serve-dense-latent", "chips": 1}
    try:
        r = serve_kind.run(cell, CONFIG, traffic, seed=2**31 + 7,
                           seconds=4.0, trace=True,
                           t_start=time.monotonic(), platform="cpu")
    finally:
        serve.delete(serve_kind.APP)
    # every number `correct` compares within its limit, but the chips:
    # a CPU rehearsal runs on as many virtual devices as its environment
    # gives it (one by hand, eight under tests/conftest.py)
    compared = dict(r["compared"])
    compared.pop("chips")
    assert all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values()), compared
    assert r["attempted"] > 0 and r["failed"] == 0
    obs = r["observed"]
    for stats in obs["polls"] + [obs["after"]["stats"]]:
        for program in ("decode", "prefill"):
            by = stats["context_by_kind"]["latent"][program]
            assert by["slots_read"] >= by["slots_valid"]
            assert by["row_slots"] >= by["rows"] * 0
    kv = obs["after"]["stats"]["kv"]["latent"]
    assert kv["latent"] and kv["select"] is None
    mean = read_layer_metric("mla_ctx_slots_per_row", obs)
    assert 24 < mean < 84  # a decode row's context: prompt + answer so far
    # the parent's engine has no such counters: the reader says nothing
    for edge in ("before", "after"):
        for program in obs[edge]["stats"]["context"].values():
            program.pop("rows"), program.pop("row_slots")
    assert read_layer_metric("mla_ctx_slots_per_row", obs) is None
