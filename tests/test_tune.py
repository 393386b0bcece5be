"""Tuner tests (reference model: tune/tests — controller, schedulers,
restore)."""

import sys

import cloudpickle
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.cluster_utils import Cluster
from ray_tpu.train.trainer import RunConfig

cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(scope="module")
def cluster():
    """Module-scoped on purpose (tier-1 wall-time lever, see ROADMAP):
    every test shares one head + nodelet + driver; trials only ever add
    actors, never nodes, so no per-test cluster surgery is needed."""
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _quadratic(config):
    # minimum at x=3; lr controls convergence speed
    x = 0.0
    for _ in range(20):
        x -= config["lr"] * 2 * (x - 3.0)
        tune.report({"objective": (x - 3.0) ** 2, "x": x})


def test_random_sweep_20_trials(cluster, tmp_path):
    tuner = tune.Tuner(
        _quadratic,
        param_space={"lr": tune.loguniform(1e-3, 0.5)},
        tune_config=tune.TuneConfig(metric="objective", mode="min",
                                    num_samples=20, seed=7,
                                    max_concurrent_trials=4),
        run_config=RunConfig(name="sweep20", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 20
    assert not grid.errors
    best = grid.get_best_result()
    assert best.metrics["objective"] < 0.5
    assert best.config["lr"] > 0.01  # higher lr converges further in 20 steps


def test_grid_search_cross_product(cluster, tmp_path):
    tuner = tune.Tuner(
        _quadratic,
        param_space={"lr": tune.grid_search([0.01, 0.1, 0.4])},
        tune_config=tune.TuneConfig(metric="objective", mode="min"),
        run_config=RunConfig(name="grid", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 3
    lrs = sorted(r.config["lr"] for r in grid)
    assert lrs == [0.01, 0.1, 0.4]


def test_asha_stops_bad_trials(cluster, tmp_path):
    def slow_loss(config):
        for i in range(30):
            tune.report({"loss": config["level"] + 0.001 * i})

    tuner = tune.Tuner(
        slow_loss,
        param_space={"level": tune.grid_search([1.0, 2.0, 3.0, 4.0,
                                                5.0, 6.0, 7.0, 8.0])},
        tune_config=tune.TuneConfig(
            metric="loss", mode="min", max_concurrent_trials=8,
            scheduler=tune.ASHAScheduler(max_t=30, grace_period=5,
                                         reduction_factor=2)),
        run_config=RunConfig(name="asha", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    stopped = [r for r in grid
               if r.metrics.get("training_iteration", 0) < 30]
    finished = [r for r in grid
                if r.metrics.get("training_iteration", 0) == 30]
    assert finished, "some trials must survive to max_t"
    assert stopped, "ASHA must cut some underperformers early"
    # the best level should be among the finishers
    assert min(r.config["level"] for r in finished) == 1.0


def test_tuner_restore_completes_pending(cluster, tmp_path):
    """Simulate an interrupted sweep: state on disk has a PENDING trial;
    restore() runs it and the grid is complete."""
    tuner = tune.Tuner(
        _quadratic,
        param_space={"lr": tune.grid_search([0.05, 0.2])},
        tune_config=tune.TuneConfig(metric="objective", mode="min"),
        run_config=RunConfig(name="resume", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 2

    # forge an interruption: mark one trial pending again
    import json
    import os

    state_file = os.path.join(str(tmp_path), "resume", "tuner_state.json")
    with open(state_file) as f:
        state = json.load(f)
    state["trials"][1]["status"] = "RUNNING"  # as if it died mid-flight
    with open(state_file, "w") as f:
        json.dump(state, f)

    restored = tune.Tuner.restore(os.path.join(str(tmp_path), "resume"),
                                  _quadratic)
    grid2 = restored.fit()
    assert len(grid2) == 2
    assert not grid2.errors
    assert all(r.metrics for r in grid2)


@pytest.mark.slow  # tier-1 budget (see ROADMAP): covered by faster siblings
def test_gpt2_tiny_lr_sweep(cluster, tmp_path):
    """The VERDICT done-criterion: sweep the GPT-2-tiny learning rate on
    CPU; best config reported (scaled to 4 trials for suite runtime)."""

    def train_gpt2(config):
        import jax
        import numpy as np
        import optax

        from ray_tpu.models.gpt2 import (
            GPT2Config,
            gpt2_loss,
            gpt2_partition_rules,
            init_gpt2,
        )
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.spmd import init_sharded_state, make_train_step

        jax.config.update("jax_platforms", "cpu")
        cfg = GPT2Config.tiny(vocab_size=256, block_size=32)
        mesh = build_mesh(MeshSpec(data=-1), devices=jax.devices())
        tx = optax.adamw(config["lr"])
        state = init_sharded_state(
            lambda: init_gpt2(jax.random.PRNGKey(0), cfg), tx, mesh,
            gpt2_partition_rules())
        rng = np.random.RandomState(0)
        toks = rng.randint(0, cfg.vocab_size, (2, cfg.block_size + 1)
                           ).astype(np.int32)
        batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        step_fn = make_train_step(lambda p, b: gpt2_loss(p, b, cfg), tx)
        with jax.set_mesh(mesh):
            for _ in range(5):
                state, metrics = step_fn(state, batch)
                tune.report({"loss": float(np.asarray(metrics["loss"]))})

    tuner = tune.Tuner(
        train_gpt2,
        param_space={"lr": tune.grid_search([1e-5, 1e-3, 5e-2, 0.5])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    max_concurrent_trials=2),
        run_config=RunConfig(name="gpt2lr", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert len(grid) == 4
    assert not grid.errors
    best = grid.get_best_result()
    assert best.config["lr"] in (1e-3, 5e-2)


# ---------------------------------------------------------------------------
# Population Based Training (VERDICT r2 item 2 / BASELINE "PBT sweep")
# ---------------------------------------------------------------------------

def _pbt_progress(config):
    """Synthetic PBT objective: score is accumulated progress `x`; good
    `lr` trials advance fast. Exploit clones x (the checkpoint) so a bad
    trial teleports to the leader's state; explore perturbs lr."""
    import time as _t

    state = tune.get_checkpoint() or {"x": 0.0}
    x = state["x"]
    for _ in range(24):
        x += config["lr"]
        tune.report({"score": x}, checkpoint={"x": x})
        _t.sleep(0.03)


_PBT_LRS = [0.001, 0.002, 0.005, 1.0]


def _run_population(scheduler, tmp_path, name):
    tuner = tune.Tuner(
        _pbt_progress,
        param_space={"lr": tune.grid_search(_PBT_LRS)},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=scheduler,
                                    max_concurrent_trials=4),
        run_config=RunConfig(name=name, storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert not grid.errors
    return sorted(r.metrics["score"] for r in grid)


@pytest.mark.slow  # tier-1 budget (see ROADMAP): covered by faster siblings
def test_pbt_beats_fixed_hyperparams(cluster, tmp_path):
    """PBT's exploit/explore lifts the population: the mean final score
    beats the same population with fixed hyperparameters."""
    fixed = _run_population(None, tmp_path, "pbt_fixed")
    pbt = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=6,
        hyperparam_mutations={"lr": [0.5, 1.0, 2.0]}, seed=3)
    evolved = _run_population(pbt, tmp_path, "pbt_evolved")
    assert pbt.exploit_count >= 1
    assert sum(evolved) > sum(fixed) * 2, (fixed, evolved)
    # the exploited stragglers specifically must have been lifted
    assert evolved[0] > fixed[0] * 10


def test_pbt_over_jax_training_smoke(cluster, tmp_path):
    """PBT over a real jitted jax train loop: checkpoints are param
    pytrees cloned across trial actors (BASELINE north star: PBT sweep
    over pod slices — here the single-host smoke)."""

    def jax_trainable(config):
        import jax
        import jax.numpy as jnp

        w = tune.get_checkpoint()
        w = jnp.asarray(w["w"]) if w else jnp.zeros(4)
        target = jnp.arange(4.0)

        @jax.jit
        def step(w, lr):
            g = 2 * (w - target)
            return w - lr * g

        for _ in range(10):
            w = step(w, config["lr"])
            loss = float(jnp.sum((w - target) ** 2))
            tune.report({"loss": loss}, checkpoint={"w": list(map(float, w))})

    pbt = tune.PopulationBasedTraining(
        metric="loss", mode="min", perturbation_interval=4,
        hyperparam_mutations={"lr": [0.05, 0.2]}, seed=0)
    tuner = tune.Tuner(
        jax_trainable,
        param_space={"lr": tune.grid_search([0.001, 0.2])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    scheduler=pbt,
                                    max_concurrent_trials=2),
        run_config=RunConfig(name="pbt_jax", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert not grid.errors
    assert grid.get_best_result().metrics["loss"] < 0.1


def test_median_stopping_rule_cuts_stragglers(cluster, tmp_path):
    sched = tune.MedianStoppingRule(metric="score", mode="max",
                                    grace_period=3)
    scores = _run_population(sched, tmp_path, "median_stop")
    assert scores[-1] > 20  # leader ran to completion
