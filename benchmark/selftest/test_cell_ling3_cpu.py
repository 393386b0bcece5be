"""Rehearsal of `serve-ling-3.0-flash-vl-reasoning-sat` on the CPU at
`tiny`: the cell's files load and say what ISSUE 61 asked, the generator
draws the sizes the traffic file states, and the ling3 family goes through
`serve.run()` and the serve kind's own runner with the reference and the
layer parity as the configuration names them (at the tiny preset's
widths). Shows control flow, counts and the correctness check; no number
from here is a metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cell_ling3_cpu.py -q -p no:cacheprovider
"""

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

# the one runtime a module (tests/test_benchmark_selftests.py collects this
# file beside that one: the same fixture object)
from benchmark.selftest.test_cell_granite_hybrid_cpu import (  # noqa: E402,F401
    cluster,
)

CELL = "serve-ling-3.0-flash-vl-reasoning-sat"
CONFIG = {
    "n_embd": 64, "n_layer": 4, "n_head": 4, "n_positions": 128,
    "vocab_size": 512,
    "model": {"family": "ling3", "preset": "tiny",
              "config": "ray_tpu.models.ling3:Ling3Config.tiny",
              "init": "ray_tpu.models.ling3:init_ling3",
              "reference": "benchmark.selftest.tiny_ling3:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 129, "max_batch_size": 4,
               "max_model_len": 128, "prefill_chunk_size": 32},
    "deployment": {"max_ongoing_requests": 8, "num_replicas": 1},
    "logprob_tolerance": 0.001,
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_was_asked():
    from benchmark import reference_ling3, run
    from benchmark.parity_mimo_v2 import program_config

    bench = _bench()
    cell, config, traffic = run.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "reasoning-sat")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced_keys"]
    assert len(config["reduced"]) == len(entry["reduced"]) == 5
    # every number of the catalog row's config under its own key, but
    # for the five that were cut
    for key, value in config["published"].items():
        assert (config[key] == value) != (key in entry["reduced"]), key
    cfg = program_config(config)
    assert cfg.n_params() == config["parameters"]
    assert list(cfg.kinds) == config["layer_types"] \
        and cfg.kinds.count("mla") == 1
    assert (cfg.n_group, cfg.topk_group, cfg.num_experts,
            cfg.experts_held) == (8, 4, 512, 16)
    assert cfg.q_lora_rank is None and config["q_lora_rank"] is None
    engine = config["engine"]
    assert (engine["max_batch_size"], engine["block_size"],
            engine["prefill_chunk_size"], engine["num_blocks"]) \
        == (64, 16, 256, 32768)
    assert engine["max_model_len"] == config["max_position_embeddings"] \
        == traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert config["deployment"]["max_ongoing_requests"] == 128
    arch = reference_ling3.published_arch()
    assert reference_ling3.layers_of(arch) == [("kda", False)] \
        + [("kda", True)] * 5 + [("mla", True)]
    assert set(config["layer_parity"]["limits"]) == {
        "kda_gate", "kda_state", "mixer", "decode_mixer", "ffn_dense",
        "ffn_experts", "routing"}
    assert config["layer_parity"]["rows"] == 8448
    # the cell is on the lists of the metrics it reports
    on = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
          if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "setup_s", "kda_share_pct",
            "kda_step_roofline_pct", "kda_chunk_roofline_pct",
            "mla_ctx_slots_per_row", "batch_occupancy",
            "moe_held_pairs_share_pct", "moe_held_share_pct",
            "ctx_kernel_steps_pct", "decode_lanes_per_step"} <= on
    assert not {"mla_dense_share_pct", "mla_decode_read_roofline_pct",
                "ssm_step_share_pct", "mhc_share_pct"} & on


def test_the_generator_draws_the_sizes_the_traffic_states():
    from benchmark import run, traffic_gen

    _, config, traffic = run.load_cell(_bench(), CELL)
    assert (traffic["loop"], traffic["clients"],
            traffic["cycle_requests"]) == ("closed", 128, 128)  # ISSUE 61's
    pool = traffic_gen.ClosedPool(traffic, 2**31 + 5, config["vocab_size"])
    spec = traffic["prompt_len"]
    # sigma 1.0: the stratified draw of 128 runs from 128 (clipped) to
    # 14,640 tokens, 58 chunks that carry S
    assert pool.plens.min() == spec["min"] \
        and 12288 < pool.plens.max() <= spec["max"]
    assert 0.93 < np.median(pool.plens) / spec["median"] < 1.07
    assert 1500 < pool.plens.mean() < 1800  # the tail of long documents
    assert (pool.plens > 4096).sum() >= 8
    other = traffic_gen.ClosedPool(traffic, 77, config["vocab_size"])
    assert sorted(other.plens) == sorted(pool.plens)
    assert 1500 < pool.olens.mean() < 1570
    assert pool.plens.max() + pool.olens.max() \
        <= config["engine"]["max_model_len"]
    req = pool.get(3)
    assert len(req.prompt) == pool.plens[3] and max(req.prompt) \
        < config["vocab_size"]


def test_serve_cell_of_state_beside_a_latent_pool(cluster, monkeypatch):
    """Closed loop through `serve.run()`: the log-probs against the plain
    reference AND the layer parity at tiny, the state's and the latent
    kind's counters in the window, and the counter-based readers."""
    from benchmark import kda_ops
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
    cell = {"name": "selftest-serve-kda-latent", "chips": 1}
    # A chunk of three KDA layers is many small operations, and on a CPU
    # that five other test workers share it went from 9 ms to over a
    # second: tier-1 once gave the 4 s window ONE token event, and the
    # slope needs two. A window that saw too little is run once more, five
    # times as long.
    for seconds in (4.0, 20.0):
        try:
            r = serve_kind.run(cell, CONFIG, traffic, seed=2**31 + 7,
                               seconds=seconds, trace=True,
                               t_start=time.monotonic(), platform="cpu")
        except ValueError as too_few:
            if seconds == 20.0 or "at least two events" not in str(too_few):
                raise
            continue
        finally:
            serve.delete(serve_kind.APP)
        steps = [r["observed"][edge]["stats"]["steps"]["decode"]
                 for edge in ("before", "after")]
        if r["attempted"] >= 2 and steps[1] - steps[0] >= 4:
            break
    compared = dict(r["compared"])
    compared.pop("chips")  # a CPU rehearsal runs on virtual devices
    assert all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values()), compared
    assert r["attempted"] > 0 and r["failed"] == 0
    obs = r["observed"]
    state = obs["after"]["stats"]["state"]
    assert state["layers"] == 3 and state["kernel_steps"] == 0
    assert state["carried"] > 0 and state["resets"] > 0
    kv = obs["after"]["stats"]["kv"]["latent"]
    assert kv["latent"] and kv["select"] is None
    assert 24 < read_layer_metric("mla_ctx_slots_per_row", obs) < 84
    assert 1 <= read_layer_metric("decode_lanes_per_step", obs) <= 4
    assert 1 <= kda_ops.prefill_rows_a_program(obs) <= 32
    # the latent kind's decode read counts its launches by kernel (none on
    # the CPU), so the accepted reader has a share to give
    assert read_layer_metric("ctx_kernel_steps_pct", obs) == 0.0
    # the CPU's trace has no device plane, the tiny preset's configuration
    # none of the family's keys: the three new readers say nothing
    for name in ("kda_share_pct", "kda_step_roofline_pct",
                 "kda_chunk_roofline_pct", "moe_held_share_pct"):
        assert read_layer_metric(name, obs) is None
