"""Rehearsal of every kind of cell on the CPU at `tiny`: the same runners,
through JaxTrainer.fit() and serve.run(), with platform="cpu". Shows control
flow, counts and the correctness checks; no number from here is a metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_cells_cpu.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

TINY = {"n_embd": 128, "n_layer": 2, "n_head": 4, "n_positions": 128,
        "vocab_size": 512}
TRAIN_CONFIG = {
    **TINY,
    "model": {
        "config": "ray_tpu.models.gpt2:GPT2Config.tiny",
        "init": "ray_tpu.models.gpt2:init_gpt2",
        "loss": "ray_tpu.models.gpt2:gpt2_loss",
        "rules": "ray_tpu.models.gpt2:gpt2_partition_rules",
        "reference_loss": "benchmark.reference_gpt2:mean_loss",
        "flops_per_token": "benchmark.flops:gpt2_train_flops_per_token"},
    "trainer": {"optimizer": {"name": "adamw", "learning_rate": 3e-4,
                              "weight_decay": 0.1},
                "loss_tolerance": 0.002},
}
SERVE_CONFIG = {
    **TINY,
    "model": {"family": "gpt2", "preset": "tiny",
              "config": "ray_tpu.models.gpt2:GPT2Config.tiny",
              "init": "ray_tpu.models.gpt2:init_gpt2",
              "reference": "benchmark.reference_gpt2:serve_reference"},
    "engine": {"block_size": 8, "num_blocks": 129, "max_batch_size": 4,
               "max_model_len": 128, "prefill_chunk_size": 32},
    "deployment": {"max_ongoing_requests": 8, "num_replicas": 1},
    "logprob_tolerance": 0.05,
}


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.mark.parametrize("chips", [1, 4])
def test_train_cell(cluster, chips):
    from benchmark.kinds import train

    traffic = {"kind": "train", "seq": 64, "batch_per_chip": 2,
               "pool_batches": 4, "warmup_steps": 2, "trace_steps": 3,
               "mesh": {"data": -1}}
    cell = {"name": f"selftest-train-{chips}", "chips": chips}
    r = train.run(cell, TRAIN_CONFIG, traffic, seed=2**31 + 11, seconds=2.0,
                  trace=True, t_start=time.monotonic(), platform="cpu")
    assert r["correct"], r
    assert r["device"]["count"] == chips
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["end_to_end"]["train_tokens_per_s_chip"] > 0
    assert r["end_to_end"]["setup_s"] > 0
    assert r["observed"]["events"]  # the CPU trace has host events at least
    assert r["observed"]["ready_s"] > 0


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_serve_cell(cluster, loop, monkeypatch, capsys):
    import json

    from benchmark import stall
    from benchmark.kinds import serve as serve_kind
    from ray_tpu import serve

    monkeypatch.setattr(serve_kind, "CHECK_PROMPT_LENS", (5, 12, 40))
    monkeypatch.setattr(serve_kind, "CHECK_MAX_TOKENS", 4)
    monkeypatch.setattr(serve_kind, "TRACE_FOR_S", 1.0)
    lens = {"prompt_len": {"dist": "uniform", "min": 8, "max": 48},
            "output_len": {"dist": "uniform", "min": 2, "max": 6}}
    traffic = {"kind": "serve", "loop": loop, "base_seed": 3, **lens,
               **({"rate_rps": 4.0, "preroll_s": 1.0} if loop == "open"
                  else {"clients": 4, "preroll_s": 0.5,
                        "cycle_requests": 8})}
    cell = {"name": f"selftest-serve-{loop}", "chips": 1}
    try:
        r = serve_kind.run(cell, SERVE_CONFIG, traffic, seed=2**31 + 5,
                           seconds=4.0, trace=True,
                           t_start=time.monotonic(), platform="cpu")
    finally:
        serve.delete(serve_kind.APP)
    assert r["correct"], r["end_to_end"]
    assert r["attempted"] > 0 and r["failed"] == 0
    e2e = r["end_to_end"]
    if loop == "open":
        assert r["attempted"] == 16
        assert r["observed"]["ttft_p85_ms"] > 0 and e2e["itl_p95_ms"] > 0
    else:
        assert e2e["serve_tokens_per_s"] > 0
    obs = r["observed"]
    assert obs["before"]["stats"]["finished_requests"] \
        < obs["after"]["stats"]["finished_requests"]
    assert obs["polls"]
    from benchmark import readers

    assert readers.histogram_mean(obs, "serve_llm_step_ms",
                                  kind="decode") > 0
    assert readers.counter_delta(obs, "preemptions") is not None
    # the window's "after" was taken when the window ended, and the trace
    # was stopped beside it
    assert obs["after_late_s"] < 1.0 and obs["trace_stop_s"] >= 0.0
    # the run's one stall line, printed and parsed back, every field there
    said = stall.parse(capsys.readouterr().out)
    assert said is not None and set(stall.FIELDS) <= set(said)
    assert said == json.loads(json.dumps(obs["stall"]))
    assert said["gaps"]["pooled"]["n"] > 0
    assert said["engine_itl_p95_ms"] > 0 and said["ticker_max_ms"] > 0
    # every program was warmed up before the window
    assert said["compiled"] == 0 and said["phase_s"]["dispatch"] > 0
    assert all({"max_ms", "over_250ms_s", "window_top_ms"} <= set(t)
               for t in said["turns"].values())
    assert (said["generator_late_ms"] > 0) == (loop == "open")
    # what `correct` compared, each beside its limit
    assert r["compared"]["logprob_gap_max_nats"]["value"] \
        <= r["compared"]["logprob_gap_max_nats"]["limit"] == 0.05
    assert r["compared"]["failed_requests"] == {"value": 0, "limit": 0}
