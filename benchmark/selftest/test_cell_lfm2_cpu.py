"""Rehearsal of `serve-lfm2-8b-a1b-rag-agent-sat` on the CPU at `tiny`: the
lfm2 family through `serve.run()` and the serve kind's own runner, as
`test_cells_cpu.py` rehearses the other kinds of cell (a file of its own:
a PR adds files to the benchmark and edits none). Shows control flow,
counts and the correctness check, both legs; no number from here is a
metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cell_lfm2_cpu.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

# the family at `tiny`, more lanes than any other rehearsal (8), chunks of
# 32 so that prompts of 24 to 100 tokens cross one to four of them
CONFIG = {
    "n_embd": 64, "n_layer": 7, "n_head": 4, "n_positions": 128,
    "vocab_size": 512,
    "model": {"family": "lfm2", "preset": "tiny",
              "config": "ray_tpu.models.lfm2:Lfm2Config.tiny",
              "init": "ray_tpu.models.lfm2:init_lfm2",
              "reference": "benchmark.selftest.tiny_lfm2:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 257, "max_batch_size": 8,
               "max_model_len": 128, "prefill_chunk_size": 32},
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


def test_serve_cell_of_the_conv_family(cluster, monkeypatch):
    """Closed loop, twice as many callers as lanes, prompts of several
    chunks: the log-probs against the plain reference and the parity leg,
    the state's account in the window, and the counter-based readers."""
    from benchmark.kinds import serve as serve_kind
    from benchmark.run import read_layer_metric
    from ray_tpu import serve

    monkeypatch.setattr(serve_kind, "CHECK_PROMPT_LENS", (5, 20, 40, 70))
    monkeypatch.setattr(serve_kind, "CHECK_MAX_TOKENS", 4)
    monkeypatch.setattr(serve_kind, "TRACE_FOR_S", 1.0)
    traffic = {"kind": "serve", "loop": "closed", "base_seed": 3,
               "clients": 16, "preroll_s": 0.5, "cycle_requests": 16,
               "prompt_len": {"dist": "uniform", "min": 24, "max": 100},
               "output_len": {"dist": "uniform", "min": 4, "max": 12}}
    cell = {"name": "selftest-serve-conv", "chips": 1}
    try:
        r = serve_kind.run(cell, CONFIG, traffic, seed=2**31 + 7,
                           seconds=4.0, trace=True,
                           t_start=time.monotonic(), platform="cpu")
    finally:
        serve.delete(serve_kind.APP)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    obs = r["observed"]
    state = obs["after"]["stats"]["state"]
    assert state["slots"] == 8 and state["layers"] == 5
    assert state["carried"] > 0 and state["resets"] > 0
    assert sum(state["decode_steps"].values()) > 0
    assert state["decode_lanes"] >= sum(state["decode_steps"].values())
    assert state["prefix_declined"] is True
    share = read_layer_metric("conv_carried_chunks_pct", obs)
    assert 20 < share < 90
    # the lanes a decode step moved on, and the rows of its programs that
    # no lane owned: 8 lanes, programs of 1 to 8 rows
    lanes = read_layer_metric("decode_lanes_per_step", obs)
    padded = read_layer_metric("decode_padded_rows_pct", obs)
    assert 1 <= lanes <= 8 and 0 <= padded < 50
    assert read_layer_metric("moe_held_pairs_share_pct", obs) > 0
    assert read_layer_metric("moe_load_imbalance", obs) >= 1.0
    assert read_layer_metric("preemptions", obs) == 0
