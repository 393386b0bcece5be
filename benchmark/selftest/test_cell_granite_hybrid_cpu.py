"""Rehearsal of `serve-granite-4.0-h-small-long-answer-sat` on the CPU at
`tiny`: the granite_hybrid family through `serve.run()` and the serve
kind's own runner, as `test_cells_cpu.py` rehearses the other kinds of
cell (a file of its own: a PR adds files to the benchmark and edits
none). Shows control flow, counts and the correctness check, both legs;
no number from here is a metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cell_granite_hybrid_cpu.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

# the family at `tiny`, 8 lanes, chunks of 32: the cell's shape of
# traffic, prompts of one or two chunks and answers several times as long
CONFIG = {
    "n_embd": 64, "n_layer": 4, "n_head": 4, "n_positions": 128,
    "vocab_size": 512,
    "model": {"family": "granite_hybrid", "preset": "tiny",
              "config": "ray_tpu.models.granite_hybrid:"
                        "GraniteHybridConfig.tiny",
              "init": "ray_tpu.models.granite_hybrid:init_granite_hybrid",
              "reference":
                  "benchmark.selftest.tiny_granite_hybrid:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 257, "max_batch_size": 8,
               "max_model_len": 128, "prefill_chunk_size": 32,
               "enable_prefix_cache": True},
    "deployment": {"max_ongoing_requests": 16, "num_replicas": 1},
    "logprob_tolerance": 0.001,
}


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_serve_cell_of_the_one_group_hybrid(cluster, monkeypatch):
    """Closed loop, twice as many callers as lanes, short prompts and
    longer answers: the log-probs against the plain reference and the
    parity legs (the engine's past a chunk's edge among them), the
    state's and the routing's accounts in the window, and the
    counter-based readers the cell lists."""
    from benchmark.kinds import serve as serve_kind
    from benchmark.run import read_layer_metric
    from ray_tpu import serve

    monkeypatch.setattr(serve_kind, "CHECK_PROMPT_LENS", (5, 20, 40, 70))
    monkeypatch.setattr(serve_kind, "CHECK_MAX_TOKENS", 4)
    monkeypatch.setattr(serve_kind, "TRACE_FOR_S", 1.0)
    traffic = {"kind": "serve", "loop": "closed", "base_seed": 3,
               "clients": 16, "preroll_s": 0.5, "cycle_requests": 16,
               "prompt_len": {"dist": "lognormal", "median": 24,
                              "sigma": 0.8, "min": 8, "max": 64},
               "output_len": {"dist": "uniform", "min": 16, "max": 40}}
    cell = {"name": "selftest-serve-granite", "chips": 1}
    try:
        r = serve_kind.run(cell, CONFIG, traffic, seed=2**31 + 11,
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
    state = obs["after"]["stats"]["state"]
    assert state["slots"] == 8 and state["layers"] == 3
    assert state["resets"] > 0 and state["carried"] > 0
    assert sum(state["decode_steps"].values()) > 0
    assert state["prefix_declined"] is True
    # answers longer than prompts: far more decode steps than prompts
    assert sum(state["decode_steps"].values()) > 3 * state["resets"]
    lanes = read_layer_metric("decode_lanes_per_step", obs)
    padded = read_layer_metric("decode_padded_rows_pct", obs)
    assert 1 <= lanes <= 8 and 0 <= padded < 50
    held = read_layer_metric("moe_held_pairs_share_pct", obs)
    assert 30 < held < 70  # 6 of 12 experts held
    assert read_layer_metric("moe_load_imbalance", obs) >= 1.0
    assert read_layer_metric("preemptions", obs) == 0
    assert read_layer_metric("batch_occupancy", obs) > 50
    # the device readers find nothing to read in a CPU trace: None, and
    # no exception
    for name in ("ssm_step_share_pct", "ssm_step_roofline_pct",
                 "ssm_scan_roofline_pct"):
        assert read_layer_metric(name, obs) is None
