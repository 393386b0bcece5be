"""1F1B pipeline-parallel train strategy (train/pipeline_strategy.py).

Schedule math is gated exactly (the per-stage fwd/bwd interleave and
the simulated bubble == (S-1)/(S-1+M)); the distributed strategy is
gated on loss parity against the single-program pipelined model and on
the bubble/microbatch metrics surfacing."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel.pipeline import (
    interleaved_1f1b_submission_order,
    one_f_one_b_schedule,
    one_f_one_b_submission_order,
    simulate_1f1b,
    simulate_interleaved_1f1b,
    theoretical_bubble,
    theoretical_bubble_interleaved,
)


# ------------------------------------------------------------- schedule


def test_1f1b_exact_interleave_2x4():
    assert one_f_one_b_schedule(2, 4) == [
        [("fwd", 0), ("fwd", 1), ("bwd", 0), ("fwd", 2), ("bwd", 1),
         ("fwd", 3), ("bwd", 2), ("bwd", 3)],
        [("fwd", 0), ("bwd", 0), ("fwd", 1), ("bwd", 1), ("fwd", 2),
         ("bwd", 2), ("fwd", 3), ("bwd", 3)],
    ]


def test_1f1b_exact_interleave_4x4_warmup_depths():
    sched = one_f_one_b_schedule(4, 4)
    # stage s runs S-1-s warmup forwards (plus the first steady-state
    # forward) before its first backward
    for s, ops in enumerate(sched):
        warm = [k for k, _ in ops[:ops.index(("bwd", 0))]]
        assert warm == ["fwd"] * (4 - s), (s, ops)
        # steady state is strictly one-forward-one-backward
        kinds = [k for k, _ in ops]
        assert kinds.count("fwd") == kinds.count("bwd") == 4
    # last stage never waits: F0 B0 F1 B1 ...
    assert sched[3] == [("fwd", 0), ("bwd", 0), ("fwd", 1), ("bwd", 1),
                        ("fwd", 2), ("bwd", 2), ("fwd", 3), ("bwd", 3)]


@pytest.mark.parametrize("S,M", [(1, 1), (1, 4), (2, 4), (3, 5),
                                 (4, 8), (4, 2), (5, 3)])
def test_1f1b_schedule_complete_and_memory_bounded(S, M):
    sched = one_f_one_b_schedule(S, M)
    for s, ops in enumerate(sched):
        assert sorted(ops) == sorted(
            [("fwd", m) for m in range(M)] + [("bwd", m)
                                             for m in range(M)])
        # 1F1B memory bound: at most min(M, S-s) forwards outstanding
        live = peak = 0
        for kind, _ in ops:
            live += 1 if kind == "fwd" else -1
            peak = max(peak, live)
        assert peak <= min(M, S - s), (s, peak, ops)


@pytest.mark.parametrize("S,M", [(1, 2), (2, 4), (3, 5), (4, 8), (4, 2)])
def test_1f1b_submission_order_topological(S, M):
    order = one_f_one_b_submission_order(S, M)
    assert len(order) == 2 * S * M
    seen = set()
    per_stage = {s: [] for s in range(S)}
    for kind, s, m in order:
        if kind == "fwd" and s > 0:
            assert ("fwd", s - 1, m) in seen
        if kind == "bwd":
            assert ("fwd", s, m) in seen
            if s < S - 1:
                assert ("bwd", s + 1, m) in seen
        seen.add((kind, s, m))
        per_stage[s].append((kind, m))
    # per-stage projection IS the 1F1B interleave
    assert [per_stage[s] for s in range(S)] == one_f_one_b_schedule(S, M)


@pytest.mark.parametrize("S,M", [(2, 4), (3, 6), (4, 8), (4, 4), (2, 1)])
def test_simulated_bubble_matches_theoretical(S, M):
    sim = simulate_1f1b(S, M)
    assert sim["bubble_ratio"] == pytest.approx(
        theoretical_bubble(S, M), abs=1e-9)
    # unequal op costs still fill: bubble stays below the equal-cost
    # GPipe worst case of (S-1)/M utilization loss at these shapes
    assert 0.0 <= simulate_1f1b(S, M, 1.0, 2.0)["bubble_ratio"] < 1.0


# ------------------------------------------- interleaved schedule math


@pytest.mark.parametrize("S,M,R", [(2, 4, 2), (2, 2, 3), (3, 6, 2),
                                   (4, 8, 2), (2, 8, 4)])
def test_interleaved_submission_complete_and_topological(S, M, R):
    """Every (kind, virtual_stage, microbatch) appears once, and each
    op's dependencies precede it — FIFO workers realize the schedule."""
    order = interleaved_1f1b_submission_order(S, M, R)
    V = S * R
    assert len(order) == 2 * V * M
    assert sorted(order) == sorted(
        [("fwd", v, m) for v in range(V) for m in range(M)]
        + [("bwd", v, m) for v in range(V) for m in range(M)])
    seen = set()
    for kind, v, m in order:
        if kind == "fwd" and v > 0:
            assert ("fwd", v - 1, m) in seen, (kind, v, m)
        if kind == "bwd":
            assert ("fwd", v, m) in seen, (kind, v, m)
            if v < V - 1:
                assert ("bwd", v + 1, m) in seen, (kind, v, m)
        seen.add((kind, v, m))


def test_interleaved_submission_rejects_m_below_s():
    with pytest.raises(ValueError):
        interleaved_1f1b_submission_order(4, 3, 2)
    with pytest.raises(ValueError):
        interleaved_1f1b_submission_order(2, 4, 0)


@pytest.mark.parametrize("S,M,R", [(2, 4, 2), (2, 4, 3), (3, 6, 2),
                                   (4, 8, 2), (4, 4, 4)])
def test_interleaved_sim_matches_theory_and_beats_flat(S, M, R):
    """The discrete-event interleaved makespan reproduces the
    (S-1)/(R*M+S-1) floor exactly, strictly below flat 1F1B's
    (S-1)/(M+S-1) at equal S and M — the whole point of V virtual
    stages per worker."""
    sim = simulate_interleaved_1f1b(S, M, R)
    assert sim["bubble_ratio"] == pytest.approx(
        theoretical_bubble_interleaved(S, M, R), abs=1e-9)
    flat = simulate_1f1b(S, M)["bubble_ratio"]
    assert sim["bubble_ratio"] < flat, (sim, flat)
    # R=1 degrades to the flat schedule
    assert simulate_interleaved_1f1b(S, M, 1)["bubble_ratio"] == \
        pytest.approx(flat, abs=1e-9)


# ------------------------------------------------------- cluster parity


@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _toy_batch(cfg, B, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "tokens": rs.randint(0, cfg.vocab_size,
                             (B, cfg.block_size)).astype(np.int32),
        "targets": rs.randint(0, cfg.vocab_size,
                              (B, cfg.block_size)).astype(np.int32),
    }


def test_pipeline_strategy_matches_single_program(cluster):
    """2 stage workers x 4 microbatches vs pipelined_train_step on a
    one-device mesh: same init, same lr, 3 SGD steps — losses and the
    merged params must track."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.models.pipelined import (
        PipelinedConfig,
        init_pipelined,
        pipelined_train_step,
    )
    from ray_tpu.train.pipeline_strategy import PipelineStrategy

    cfg = PipelinedConfig()
    batch = _toy_batch(cfg, B=8)
    params = init_pipelined(jax.random.PRNGKey(0), cfg)
    ref_mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("pipe", "fsdp"))
    ref_step = pipelined_train_step(cfg, ref_mesh, lr=1e-2)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    ref_params, ref_losses = params, []
    for _ in range(3):
        ref_params, loss = ref_step(ref_params, jb)
        ref_losses.append(float(loss))

    ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=4,
                          lr=1e-2, seed=0)
    try:
        metrics = [ps.train_step(batch) for _ in range(3)]
        pipe_losses = [m["loss"] for m in metrics]
        np.testing.assert_allclose(ref_losses, pipe_losses, atol=1e-5)
        assert pipe_losses[0] > pipe_losses[-1]  # it trains
        merged = ps.full_params()
        for a, b in zip(jax.tree.leaves(ref_params),
                        jax.tree.leaves(merged)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        for m in metrics:
            # busy is the stage processes' CPU time: XLA:CPU's own
            # threads can make it exceed S x the wall window here, so
            # only "some op was counted" holds on every host
            assert m["bubble_ratio"] < 1.0
            assert m["bubble_theoretical"] == pytest.approx(
                theoretical_bubble(2, 4))
            assert m["microbatches"] == 4
    finally:
        ps.shutdown()


def test_pipeline_metrics_surface(cluster):
    """bubble gauge + microbatch counter reach the metric registry."""
    from ray_tpu.models.pipelined import PipelinedConfig
    from ray_tpu.train.pipeline_strategy import (
        PipelineStrategy,
        _strategy_metrics,
    )

    cfg = PipelinedConfig(n_virtual_stages=2, d_model=32, d_ff=64,
                          block_size=16)
    ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=2,
                          lr=1e-2)
    try:
        m_bubble, m_micro, m_virtual = _strategy_metrics()
        before = m_micro._values.get((), 0.0)
        out = ps.train_step(_toy_batch(cfg, B=4))
        assert m_micro._values.get((), 0.0) == before + 2
        exposed = "\n".join(m_bubble.expose())
        assert "train_pipeline_bubble_ratio" in exposed
        exposed_v = "\n".join(m_virtual.expose())
        assert "train_pipeline_virtual_stages" in exposed_v
        assert m_virtual._values.get((), 0.0) == 2.0  # flat: V == S
        assert out["loss"] > 0
    finally:
        ps.shutdown()


def test_jax_trainer_pipeline_strategy(cluster, tmp_path):
    """JaxTrainer(strategy='pipeline') drives the strategy end-to-end
    and returns a Result with per-step history."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg_kwargs = dict(n_virtual_stages=2, d_model=32, d_ff=64,
                      block_size=16, num_microbatches=2)
    from ray_tpu.models.pipelined import PipelinedConfig

    batch = _toy_batch(PipelinedConfig(**cfg_kwargs), B=4)
    result = JaxTrainer(
        strategy="pipeline",
        train_loop_config={"model": cfg_kwargs, "batch": batch,
                           "steps": 2, "num_stages": 2, "lr": 1e-2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="pipe_t", storage_path=str(tmp_path)),
    ).fit()
    assert len(result.metrics_history) == 2
    assert result.metrics["loss"] > 0
    assert "bubble_ratio" in result.metrics


def test_pipeline_strategy_rejects_bad_shapes(cluster):
    from ray_tpu.models.pipelined import PipelinedConfig
    from ray_tpu.train.pipeline_strategy import PipelineStrategy

    cfg = PipelinedConfig(n_virtual_stages=2, d_model=32, d_ff=64,
                          block_size=16)
    with pytest.raises(ValueError):
        # more stages than blocks
        PipelineStrategy(cfg, num_stages=3, num_microbatches=2)
    ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=3)
    try:
        with pytest.raises(ValueError):
            ps.train_step(_toy_batch(cfg, B=4))  # 4 % 3 != 0
    finally:
        ps.shutdown()


# --------------------------------------- interleaved + ZeRO composition


def _single_program_reference(cfg, batch, steps, lr=1e-2, seed=0):
    """pipelined_train_step on a 1-device mesh — the parity oracle all
    strategy configurations (flat, interleaved, composed) must match."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.models.pipelined import (
        init_pipelined,
        pipelined_train_step,
    )

    params = init_pipelined(jax.random.PRNGKey(seed), cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("pipe", "fsdp"))
    step = pipelined_train_step(cfg, mesh, lr=lr)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    losses = []
    for _ in range(steps):
        params, loss = step(params, jb)
        losses.append(float(loss))
    return params, losses


def test_interleaved_strategy_matches_single_program(cluster):
    """num_repeats=2 (V=4 virtual stages on 2 workers): the circular
    schedule must be numerically invisible — same losses and merged
    params as the single-program oracle, and the metrics surface the
    interleaved theoretical floor."""
    import jax

    from ray_tpu.models.pipelined import PipelinedConfig
    from ray_tpu.train.pipeline_strategy import PipelineStrategy

    cfg = PipelinedConfig()
    batch = _toy_batch(cfg, B=8, seed=2)
    ref_params, ref_losses = _single_program_reference(cfg, batch, 3)

    ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=4,
                          lr=1e-2, seed=0, num_repeats=2)
    try:
        metrics = [ps.train_step(batch) for _ in range(3)]
        np.testing.assert_allclose(
            ref_losses, [m["loss"] for m in metrics], atol=1e-5)
        merged = ps.full_params()
        for a, b in zip(jax.tree.leaves(ref_params),
                        jax.tree.leaves(merged)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        for m in metrics:
            assert m["num_repeats"] == 2
            assert m["virtual_stages"] == 4
            assert m["bubble_theoretical"] == pytest.approx(
                theoretical_bubble_interleaved(2, 4, 2))
    finally:
        ps.shutdown()


def test_pipeline_zero_composition_parity_and_bytes(cluster):
    """One config, every axis: interleaved (R=2) pipeline with
    intra-stage ZeRO over data_parallel=2. Losses must still match the
    single-program oracle (ZeRO is a memory layout, not an algorithm
    change), and the per-stage resident grad/param bytes must land at
    ~1/D of the undistributed run's."""
    from ray_tpu.models.pipelined import PipelinedConfig
    from ray_tpu.train.pipeline_strategy import PipelineStrategy

    cfg = PipelinedConfig()
    batch = _toy_batch(cfg, B=8, seed=4)
    _, ref_losses = _single_program_reference(cfg, batch, 3)

    def run(zero_stage, data_parallel):
        ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=4,
                              lr=1e-2, seed=0, num_repeats=2,
                              zero_stage=zero_stage,
                              data_parallel=data_parallel,
                              momentum=0.9)
        try:
            losses = [ps.train_step(batch)["loss"] for _ in range(3)]
            return losses, ps.last_stage_stats
        finally:
            ps.shutdown()

    base_losses, base_stats = run(0, 1)
    z_losses, z_stats = run(3, 2)
    # momentum=0.9 diverges from the momentum-0 oracle — compare the
    # two momentum runs to each other, and the first (pre-update) loss
    # to the oracle's
    assert z_losses[0] == pytest.approx(ref_losses[0], abs=1e-5)
    np.testing.assert_allclose(base_losses, z_losses, atol=1e-5)
    D, bound = 2, 1.25 / 2
    for b, z in zip(base_stats, z_stats):
        assert z["grad_state_bytes"] / b["grad_state_bytes"] <= bound
        assert z["param_state_bytes"] / b["param_state_bytes"] <= bound
        assert z["velocity_state_bytes"] / b["velocity_state_bytes"] \
            <= bound


# ----------------------------------------------------------- checkpoint


def test_pipeline_checkpoint_round_trip(cluster, tmp_path):
    """save_checkpoint writes per-stage shards + manifest;
    load_pipeline_checkpoint reassembles the exact full param tree."""
    import jax

    from ray_tpu.models.pipelined import PipelinedConfig
    from ray_tpu.train.pipeline_strategy import (
        PipelineStrategy,
        load_pipeline_checkpoint,
    )

    cfg = PipelinedConfig(d_model=32, d_ff=64, block_size=16)
    ps = PipelineStrategy(cfg, num_stages=2, num_microbatches=2,
                          lr=1e-2, seed=0, num_repeats=2)
    try:
        ps.train_step(_toy_batch(cfg, B=4))
        ckpt = ps.save_checkpoint(str(tmp_path / "ck"))
        want = ps.full_params()
    finally:
        ps.shutdown()
    got, meta = load_pipeline_checkpoint(ckpt.path)
    assert meta["format"] == "pipeline-stage-shards-v1"
    assert meta["num_stages"] == 2 and meta["num_repeats"] == 2
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restore path: a fresh strategy seeded from the checkpoint params
    # continues from the same weights
    ps2 = PipelineStrategy(cfg, num_stages=2, num_microbatches=2,
                           lr=1e-2, params=got)
    try:
        for a, b in zip(jax.tree.leaves(got),
                        jax.tree.leaves(ps2.full_params())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        ps2.shutdown()


def test_jax_trainer_pipeline_checkpoints(cluster, tmp_path):
    """JaxTrainer(strategy='pipeline') registers stage-shard
    checkpoints through CheckpointManager and hands back the latest."""
    from ray_tpu.train import (
        CheckpointConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )
    from ray_tpu.train.pipeline_strategy import load_pipeline_checkpoint

    cfg_kwargs = dict(n_virtual_stages=4, d_model=32, d_ff=64,
                      block_size=16, num_microbatches=2)
    from ray_tpu.models.pipelined import PipelinedConfig

    batch = _toy_batch(PipelinedConfig(**cfg_kwargs), B=4)
    result = JaxTrainer(
        strategy="pipeline",
        train_loop_config={"model": cfg_kwargs, "batch": batch,
                           "steps": 2, "num_stages": 2,
                           "num_repeats": 2, "lr": 1e-2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="pipe_ck", storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(num_to_keep=1)),
    ).fit()
    assert result.checkpoint is not None
    params, meta = load_pipeline_checkpoint(result.checkpoint.path)
    assert meta["num_repeats"] == 2
    assert jax_leaf_count(params) > 0


def jax_leaf_count(tree):
    import jax

    return len(jax.tree.leaves(tree))
