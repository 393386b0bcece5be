"""Rehearsal of `serve-glm-5-long-context-sat` on the CPU at `tiny`: the
glm_dsa family through `serve.run()` and the serve kind's own runner, as
`test_cells_cpu.py` rehearses the other kinds of cell (a file of its own:
a PR adds files to the benchmark and edits none). Shows control flow,
counts and the correctness check; no number from here is a metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cell_glm_dsa_cpu.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

# the latent kind's cell: the family at `tiny` (16 slots chosen a row),
# prompts of 24 to 72 tokens so that most rows refuse slots
CONFIG = {
    "n_embd": 64, "n_layer": 3, "n_head": 4, "n_positions": 128,
    "vocab_size": 512,
    "model": {"family": "glm_dsa", "preset": "tiny",
              "config": "ray_tpu.models.glm_dsa:GlmDsaConfig.tiny",
              "init": "ray_tpu.models.glm_dsa:init_glm_dsa",
              "reference": "benchmark.selftest.tiny_glm_dsa:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 129, "max_batch_size": 4,
               "max_model_len": 128, "prefill_chunk_size": 32},
    "deployment": {"max_ongoing_requests": 8, "num_replicas": 1},
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


def test_serve_cell_of_the_latent_kind(cluster, monkeypatch):
    """Closed loop, contexts several times the slots a row may choose:
    the log-probs against the plain reference, the latent kind's counters
    in every poll of the window, and the counter-based reader."""
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
    cell = {"name": "selftest-serve-latent", "chips": 1}
    try:
        r = serve_kind.run(cell, CONFIG, traffic, seed=2**31 + 7,
                           seconds=4.0, trace=True,
                           t_start=time.monotonic(), platform="cpu")
    finally:
        serve.delete(serve_kind.APP)
    assert r["correct"], r["end_to_end"]
    assert r["attempted"] > 0 and r["failed"] == 0
    obs = r["observed"]
    for stats in obs["polls"] + [obs["after"]["stats"]]:
        for by in stats["context_by_kind"]["latent"].values():
            assert by["slots_scored"] >= by["slots_valid"] \
                >= by["slots_selected"]
    assert obs["after"]["stats"]["kv"]["latent"]["select"] == 16
    share = read_layer_metric("dsa_selected_share_pct", obs)
    assert 10 < share < 90
