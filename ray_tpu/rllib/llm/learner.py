"""GRPO-style LLM learner over the train/ SPMD machinery.

The update is ONE jitted program built by `train.spmd.make_train_step`
— the same TrainState/partition-rules/batch-sharding path the
supervised trainer uses (learner mesh: batch sharded over the data
axis, params replicated or rule-sharded, GSPMD inserting the gradient
collectives) — with a GRPO policy-gradient loss instead of next-token
cross entropy:

    ratio  = exp(logp_new - logp_old)          per generated token
    adv    = (r - mean_group) / (std_group+ε)  per sequence (GRPO)
    loss   = -mean over generated tokens of
             min(ratio * adv, clip(ratio, 1±ε_clip) * adv)

`logp_old` comes from the serve.llm engine's rollout stream (the
behaviour policy at the tagged weight version), so the clipped
importance ratio absorbs exactly one flywheel lap of staleness; the
**staleness guard** drops trajectories that are older than
`max_staleness` versions or tagged stale (mixed weight versions) —
their logprobs are not reproducible at any single version, and feeding
them in corrupts the ratios silently.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

# forces jax_threefry_partitionable before any param init (same init-
# parity invariant as rllib/learner.py — see the note there)
import ray_tpu.parallel.mesh  # noqa: F401
from ray_tpu.rllib.llm.trajectory import (
    Trajectory,
    group_relative_advantages,
    to_train_batch,
)


@dataclasses.dataclass
class LLMLearnerConfig:
    lr: float = 1e-3
    clip_eps: float = 0.2  # PPO-style ratio clip
    grad_clip: float = 1.0
    group_eps: float = 1e-6  # GRPO advantage denominator
    # trajectories sampled more than this many weight versions before
    # the CURRENT learner version are dropped (0 = on-policy only; the
    # synchronous flywheel produces staleness 0, pipelined rollouts 1)
    max_staleness: int = 1
    # sampling temperature the rollouts ran at; logp_new is scaled the
    # same way so ratio == 1 at zero divergence
    temperature: float = 1.0


class LLMLearner:
    """Owns params + optimizer for one model family ("gpt2"/"llama");
    `update(trajectories)` runs one jitted GRPO step and bumps the
    weight version; `publish_weights()` hands the new version to the
    serving side (through the object store when a runtime is up)."""

    def __init__(self, model: str = "gpt2", model_config: Any = None,
                 *, params: Any = None, mesh=None,
                 config: LLMLearnerConfig | None = None, seed: int = 0):
        from ray_tpu.models import gpt2, llama
        from ray_tpu.train.spmd import TrainState, make_train_step

        families = {
            "gpt2": (gpt2.gpt2_forward, gpt2.init_gpt2,
                     gpt2.gpt2_partition_rules, gpt2.GPT2Config.tiny),
            "llama": (llama.llama_forward, llama.init_llama,
                      llama.llama_partition_rules, llama.LlamaConfig.tiny),
        }
        if model not in families:
            raise ValueError(
                f"unknown model {model!r}; have {sorted(families)}")
        forward, init_fn, rules_fn, default_cfg = families[model]
        self.model = model
        self.cfg = model_config if model_config is not None \
            else default_cfg()
        self.config = config or LLMLearnerConfig()
        self.mesh = mesh
        self._forward = forward
        self._rules = rules_fn()
        self.version = 0  # last PUBLISHED weight version
        self.tx = optax.chain(
            optax.clip_by_global_norm(self.config.grad_clip),
            optax.adam(self.config.lr),
        )
        if params is None:
            params = init_fn(jax.random.PRNGKey(seed), self.cfg)
        if mesh is not None:
            from ray_tpu.parallel.sharding import shard_pytree

            params = shard_pytree(params, self._rules, mesh)
        # optimizer moments are zeros_like(params): they inherit the
        # param shardings, same layout state_shardings would pick
        self.state = TrainState.create(params, self.tx)

        cfg = self.config
        vocab = self.cfg.vocab_size
        temp = max(cfg.temperature, 1e-6)

        def loss_fn(params, batch):
            logits = forward(params, batch["inputs"], self.cfg)
            logp_all = jax.nn.log_softmax(
                logits[..., :vocab] / temp, axis=-1)
            lp = jnp.take_along_axis(
                logp_all, batch["targets"][..., None], axis=-1)[..., 0]
            mask = batch["mask"]
            ratio = jnp.exp(lp - batch["old_logprobs"]) * mask
            adv = batch["advantages"][:, None]
            surr = jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv)
            denom = jnp.maximum(mask.sum(), 1.0)
            return -(surr * mask).sum() / denom

        self._train_step = make_train_step(loss_fn, self.tx)
        self._build_metrics()

    # ----------------------------------------------------------- metrics

    def _build_metrics(self):
        from ray_tpu.util.metrics import Counter, Histogram

        tags = {"model": self.model}
        self._m_tags = tags
        self._m_staleness = Histogram(
            "rl_traj_staleness",
            "Weight-version lag (learner version - trajectory version) "
            "of trajectories offered to the learner",
            boundaries=(0, 1, 2, 3, 5, 8), tag_keys=("model",))
        self._m_dropped = Counter(
            "rl_traj_dropped_total",
            "Trajectories dropped by the staleness guard",
            tag_keys=("model", "reason"))

    # ------------------------------------------------------------ update

    def filter_stale(self, trajs: list[Trajectory]
                     ) -> tuple[list[Trajectory], dict]:
        """The staleness guard. Observes rl_traj_staleness for every
        offered trajectory, drops `stale` (mixed-version) ones and ones
        more than `max_staleness` versions behind the current learner
        version; returns (kept, drop-count dict)."""
        kept: list[Trajectory] = []
        dropped = {"stale": 0, "too_old": 0}
        for t in trajs:
            lag = self.version - t.weight_version
            self._m_staleness.observe(max(0, lag), tags=self._m_tags)
            if t.stale:
                dropped["stale"] += 1
            elif lag > self.config.max_staleness:
                dropped["too_old"] += 1
            else:
                kept.append(t)
        for reason, n in dropped.items():
            if n:
                self._m_dropped.inc(
                    n, tags={"model": self.model, "reason": reason})
        return kept, dropped

    def _check_temperature(self, trajs: list[Trajectory]) -> None:
        """The loss scales logp_new by config.temperature; rollout
        logprobs were recorded at each trajectory's own τ (greedy
        records the unscaled policy log-prob, i.e. effective τ=1). A
        mismatch silently biases every importance ratio, so fail loud
        instead of training on corrupted ratios."""
        want = max(self.config.temperature, 1e-6)
        for t in trajs:
            eff = t.temperature if t.temperature > 0 else 1.0
            if abs(eff - want) > 1e-6:
                raise ValueError(
                    f"trajectory sampled at temperature {eff} but the "
                    f"learner is configured for {want}: importance "
                    f"ratios would be systematically biased — set "
                    f"RolloutConfig.temperature == "
                    f"LLMLearnerConfig.temperature")

    def update(self, trajs: list[Trajectory]) -> dict:
        """One GRPO step over a trajectory batch: staleness guard →
        group-relative advantages → jitted clipped policy-gradient
        update. Bumps the published weight version."""
        from ray_tpu.util import tracing

        t0 = time.perf_counter()
        with tracing.span("rl.learner_update"):
            kept, dropped = self.filter_stale(trajs)
            self._check_temperature(kept)
            if not kept:
                return {"skipped": True, "kept": 0,
                        "dropped_stale": dropped["stale"],
                        "dropped_too_old": dropped["too_old"]}
            adv = group_relative_advantages(kept, self.config.group_eps)
            batch = to_train_batch(kept, adv,
                                   max_len=self.cfg.block_size)
            if self.mesh is not None:
                from ray_tpu.train.spmd import batch_shardings

                batch = jax.device_put(
                    batch, batch_shardings(self.mesh, batch))
                with jax.set_mesh(self.mesh):
                    self.state, metrics = self._train_step(self.state,
                                                           batch)
            else:
                self.state, metrics = self._train_step(self.state, batch)
            self.version += 1
        rewards = np.asarray([t.reward for t in kept], np.float32)
        return {
            "loss": float(np.asarray(metrics["loss"])),
            "grad_norm": float(np.asarray(metrics["grad_norm"])),
            "version": self.version,
            "kept": len(kept),
            "dropped_stale": dropped["stale"],
            "dropped_too_old": dropped["too_old"],
            "reward_mean": float(rewards.mean()),
            "reward_std": float(rewards.std()),
            "update_seconds": time.perf_counter() - t0,
        }

    # ----------------------------------------------------------- weights

    def get_weights(self):
        """Host-side float32 copy of the params pytree."""
        return jax.tree.map(np.asarray, self.state.params)

    def publish_weights(self) -> tuple[int, Any]:
        """(version, weights-or-ref) for the serving side. With a
        runtime initialized the params go through the object store —
        ONE put, every replica pulls the same ref via
        `DeploymentHandle.update_weights(version, ref)`; in-process
        callers (bench, tests) get the pytree directly."""
        import ray_tpu

        w = self.get_weights()
        if ray_tpu.is_initialized():
            return self.version, ray_tpu.put(w)
        return self.version, w

    def teacher_forced_logprobs(self, traj: Trajectory,
                                params: Any = None) -> np.ndarray:
        """Per-generated-token log-probs of `traj` under a teacher-
        forced forward at `params` (default: current learner params),
        scaled by the TRAJECTORY's own sampling temperature (greedy
        recorded the unscaled policy log-prob, so τ=0 maps to 1) —
        exactly how the engine recorded them. For a non-stale
        trajectory whose weight_version matches the params, these
        reproduce `traj.logprobs` — the determinism contract RL.md
        documents and tests gate."""
        from ray_tpu.serve.llm.runner import logprob_at

        p = self.state.params if params is None else params
        seq = np.asarray([traj.prompt + traj.tokens], np.int32)
        logits = np.asarray(
            self._forward(p, jnp.asarray(seq), self.cfg),
            np.float64)[0]
        g0 = len(traj.prompt) - 1
        # the engine records logprobs with the same shared logprob_at,
        # so the contract holds by construction
        out = [logprob_at(logits[g0 + i], tok, traj.temperature,
                          self.cfg.vocab_size)
               for i, tok in enumerate(traj.tokens)]
        return np.asarray(out, np.float64)
