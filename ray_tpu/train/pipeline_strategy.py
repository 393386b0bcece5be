"""1F1B pipeline-parallel train strategy over a WorkerGroup — flat and
interleaved schedules, composed with intra-stage ZeRO sharding.

The in-program pipeline (parallel/pipeline.py schedules inside one SPMD
program) shares one jitted program across every device. This module is
the MPMD promotion ("Scaling Deep Learning Training with MPMD Pipeline
Parallelism"; Megatron schedules.py is the reference order): each
pipeline STAGE is its own worker actor holding only its stage's
parameters, and activations/grad-activations stream stage-to-stage
through the object store — same-node neighbors ride the shm fast path
(the PR 11 channel transport), cross-node neighbors the nodelet pull
path, with no driver byte-copies either way (the driver only wires
ObjectRefs).

Scheduling is deliberately SUBMISSION-ORDER-IS-EXECUTION-ORDER: stage
workers run FIFO (max_concurrency=1), the driver submits each stage's
calls in its exact schedule order, and every call's input is an
ObjectRef produced by an earlier submission (the submission orders are
topological) — so the gang executes the textbook interleave and the
whole schedule is testable as data. Two schedules:

- flat 1F1B (`one_f_one_b_submission_order`): bubble (S-1)/(S-1+M);
- interleaved (`num_repeats=R > 1`,
  `interleaved_1f1b_submission_order`): each worker owns R VIRTUAL
  stages placed round-robin (virtual stage v on worker v % S — the MPMD
  face of `pipeline_apply_interleaved`'s circular schedule), each op
  costs ~1/R of a flat-stage op, and the fill/drain bubble drops to
  (S-1)/(R*M + S-1) at the SAME stage and microbatch counts.

ZeRO composes per stage (`zero_stage`, `data_parallel=D`): each stage
worker owns a D-device data-parallel group (one process per host, all
its chips — the TPU-native shape) and runs its stage program under
GSPMD with the train/spmd.py ladder layouts: grads are pinned to the
replicated layout then reduce-scattered 1/D (stage >= 2 keeps the
accumulated grads resident scattered between microbatches), momentum
state lives 1/D (stage >= 1), and resident params live 1/D with a
just-in-time gather inside the stage program (stage 3).

The bubble is measured, not assumed: each stage reports per-op busy
time and its step window; `train_step` computes
``bubble_ratio = 1 - busy / (S * makespan)`` and surfaces it on the
`train_pipeline_bubble_ratio` gauge. Busy is the stage process's CPU
time inside its ops (`time.process_time`), not the wall span: on a
host that timeshares stage workers over fewer cores, wall spans absorb
wait-for-CPU and overstate useful work (schedules with more overlap
read as artificially bubble-free); CPU time counts only compute
actually done, and the two coincide on the deployment shape this
models — one dedicated chip group per stage worker (watchtower's
`train-pipeline-bubble` rule pages when a mis-sized microbatch count
wastes chips), alongside `train_pipeline_virtual_stages` (S*R). The
theoretical floors come from `parallel.pipeline.theoretical_bubble`
and `theoretical_bubble_interleaved`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

import cloudpickle
import numpy as np

from ray_tpu.parallel.pipeline import (
    interleaved_1f1b_submission_order,
    one_f_one_b_submission_order,
    theoretical_bubble,
    theoretical_bubble_interleaved,
)

_bubble_gauge = None
_micro_counter = None
_virtual_gauge = None


def _strategy_metrics():
    global _bubble_gauge, _micro_counter, _virtual_gauge
    if _bubble_gauge is None:
        from ray_tpu.util.metrics import Counter, Gauge

        _bubble_gauge = Gauge(
            "train_pipeline_bubble_ratio",
            "Measured 1F1B pipeline bubble fraction of the last step: "
            "1 - stage-busy / (stages * makespan); compare against "
            "(S-1)/(S-1+M) flat or (S-1)/(R*M+S-1) interleaved")
        _micro_counter = Counter(
            "train_microbatches_total",
            "Microbatches executed by the pipeline train strategy")
        _virtual_gauge = Gauge(
            "train_pipeline_virtual_stages",
            "Virtual pipeline stages (stages * repeats) of the running "
            "pipeline strategy — >num_stages means the interleaved "
            "schedule is active")
    return _bubble_gauge, _micro_counter, _virtual_gauge


class PipelineStageWorker:
    """Actor owning ONE pipeline stage: its parameter chunks (R virtual
    stages when interleaved), the 1F1B forward/backward for each
    microbatch (residuals kept per in-flight (repeat, microbatch) via
    rematerialized vjp), grad accumulation in the ZeRO layout, and the
    end-of-step SGD(+momentum) update. Methods execute FIFO — the
    driver's submission order is the schedule."""

    def __init__(self, rank: int, world_size: int):
        self.stage = rank
        self.num_stages = world_size
        self.num_repeats = 1
        self.zero_stage = 0
        self.data_parallel = 1
        self.momentum = 0.0
        self.cfg = None
        self.params = None          # list over repeats of chunk trees
        self.mesh = None            # (data, fsdp) mesh when D > 1
        self.lr = 0.0
        self.num_microbatches = 1
        self._saved: dict[tuple[int, int], Any] = {}  # (r, mb) -> residual
        self._jfwd: dict[int, Any] = {}
        self._jbwd: dict[int, Any] = {}
        self._jupd = None
        self._grads: list[Any] = []      # per repeat, ZeRO layout
        self._vel: list[Any] | None = None
        self._spans: list[tuple[float, float]] = []
        self._cpu_busy = 0.0        # work seconds inside ops (see busy_s)
        self._last_state_bytes: dict[str, int] = {}

    def ensure_cpu_devices(self, n: int) -> bool:
        """Give this worker >= n virtual CPU devices for its intra-stage
        data-parallel group (the test/laptop stand-in for a worker's
        local TPU chips). Must run before the first array op — the flag
        only counts at backend init, which load_stage triggers."""
        import os

        n = int(n)
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
        import jax

        return len(jax.local_devices()) >= n

    # ------------------------------------------------------------------

    def load_stage(self, cfg_kwargs: dict, params_blob: bytes, lr: float,
                   num_microbatches: int, num_repeats: int = 1,
                   zero_stage: int = 0, data_parallel: int = 1,
                   momentum: float = 0.0) -> int:
        """Install this worker's config + its R virtual-stage param
        chunks (params_blob: cloudpickled list, chunk r == virtual
        stage r*S + stage). Returns the worker's parameter count (the
        driver logs the split)."""
        import jax

        from ray_tpu.models.pipelined import PipelinedConfig

        self.cfg = PipelinedConfig(**cfg_kwargs)
        self.lr = float(lr)
        self.num_microbatches = int(num_microbatches)
        self.num_repeats = int(num_repeats)
        self.zero_stage = int(zero_stage)
        self.data_parallel = int(data_parallel)
        self.momentum = float(momentum)
        chunks = cloudpickle.loads(params_blob)
        if not isinstance(chunks, list):  # single-chunk (flat) callers
            chunks = [chunks]
        if self.data_parallel > 1:
            from jax.sharding import Mesh

            devs = jax.local_devices()
            if len(devs) < self.data_parallel:
                raise ValueError(
                    f"stage {self.stage}: data_parallel="
                    f"{self.data_parallel} needs that many local "
                    f"devices, have {len(devs)}")
            self.mesh = Mesh(
                np.array(devs[:self.data_parallel]).reshape(-1, 1),
                ("data", "fsdp"))
        # params enter resident in their ZeRO layout: 1/D when stage 3,
        # replicated otherwise
        self.params = [
            jax.device_put(
                jax.tree.map(jax.numpy.asarray, c),
                self._layout(c, sharded=self.zero_stage >= 3))
            for c in chunks
        ]
        self._grads = [None] * self.num_repeats
        if self.momentum:
            self._vel = [
                jax.device_put(
                    jax.tree.map(lambda a: np.zeros_like(np.asarray(a)),
                                 c),
                    self._layout(c, sharded=self.zero_stage >= 1))
                for c in chunks
            ]
        self._build_programs()
        return sum(int(np.prod(x.shape))
                   for c in self.params for x in jax.tree.leaves(c))

    def _layout(self, tree, sharded: bool):
        """NamedShardings for a chunk tree: the +data-axis 1/D ZeRO
        layout when `sharded` (and a data mesh exists), else replicated
        over the stage's device group. Without a mesh: no-op layouts
        (plain single-device placement)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mesh is None:
            dev = jax.local_devices()[0]
            return jax.tree.map(lambda _: dev, tree)
        if not sharded:
            return jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), tree)
        from ray_tpu.parallel.sharding import PartitionRules
        from ray_tpu.train.spmd import zero1_shardings

        # catch-all replicated rules: the stage's base layout is
        # replicated over its data group, so the ZeRO layout is purely
        # the +data axis on the first evenly-divisible dim
        return zero1_shardings(PartitionRules([]), tree, self.mesh,
                               data_axis="data")

    def _constrain(self, tree, layouts):
        import jax

        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            layouts)

    def _build_programs(self):
        """Per-repeat jitted forward + jitted REMATERIALIZED backward
        (the backward re-runs the chunk forward under vjp instead of
        keeping live residual closures — so both directions hit the XLA
        compile cache across microbatches/steps, and the only
        per-microbatch state parked between fwd and bwd is the chunk's
        input activation, exactly the 1F1B memory shape). Virtual stage
        v = r*S + stage; chunk 0 embeds, chunk V-1 computes the loss.
        ZeRO composition happens here: stage-3 params are gathered
        just-in-time inside both programs (pinned to the replicated
        layout so partitioning matches the unsharded program), and the
        backward emits dparams pinned replicated then reduce-scattered
        1/D when zero_stage >= 2."""
        import jax

        from ray_tpu.models.pipelined import stage_apply

        S, R = self.num_stages, self.num_repeats
        V = S * R

        for r in range(R):
            v = r * S + self.stage
            first, last = v == 0, v == V - 1

            def fn(p, x, t, _v=v):
                if self.zero_stage >= 3 and self.mesh is not None:
                    p = self._constrain(p, self._layout(p, sharded=False))
                return stage_apply(self.cfg, p, _v, V, x, targets=t,
                                   mesh=self.mesh)

            if last:
                self._jfwd[r] = jax.jit(fn)
            else:
                self._jfwd[r] = jax.jit(
                    lambda p, x, _fn=fn: _fn(p, x, None))

            def bwd(p, x, t, g, _fn=fn, _first=first, _last=last):
                if _last:
                    _, vjp = jax.vjp(
                        lambda pp, xx: _fn(pp, xx, t), p, x)
                else:
                    _, vjp = jax.vjp(
                        lambda pp, xx: _fn(pp, xx, None), p, x)
                dparams, dx = vjp(g)
                if _first:
                    # chunk 0's input is int tokens: drop the float0
                    # cotangent instead of shipping it
                    dx = None
                if self.mesh is not None:
                    # replicated pin, THEN the ZeRO scatter — the same
                    # double constraint that keeps spmd.py parity exact
                    dparams = self._constrain(
                        dparams, self._layout(dparams, sharded=False))
                    if self.zero_stage >= 2:
                        dparams = self._constrain(
                            dparams, self._layout(dparams, sharded=True))
                return dparams, dx

            self._jbwd[r] = jax.jit(bwd)

        def update(p, g, v):
            if v is not None:
                v = jax.tree.map(
                    lambda vv, gg: self.momentum * vv + gg, v, g)
                g_eff = v
            else:
                g_eff = g
            new_p = jax.tree.map(lambda pp, gg: pp - self.lr * gg,
                                 p, g_eff)
            if self.mesh is not None:
                new_p = self._constrain(
                    new_p,
                    self._layout(new_p, sharded=self.zero_stage >= 3))
                if v is not None:
                    v = self._constrain(
                        v, self._layout(v, sharded=self.zero_stage >= 1))
            return new_p, v

        self._jupd = jax.jit(update)

    # ------------------------------------------------------------------

    def _put_batch(self, arr):
        """Device-put an activation/batch leaf sharded over the stage's
        data group (leading dim), or plainly without a mesh."""
        import jax
        import jax.numpy as jnp

        x = jnp.asarray(arr)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(x, NamedSharding(self.mesh, P("data")))
        return x

    def forward(self, r: int, mb: int, payload, targets=None):
        """Forward one microbatch through virtual stage r*S + stage:
        payload is tokens (virtual stage 0) or the previous virtual
        stage's activation. Returns the activation for the next virtual
        stage, or the microbatch loss on the last. The inputs park as
        residuals until `backward(r, mb)`."""
        import jax

        t0 = time.perf_counter()
        c0 = time.process_time()
        v = r * self.num_stages + self.stage
        last = v == self.num_stages * self.num_repeats - 1
        x = self._put_batch(payload)
        if last:
            tgt = self._put_batch(targets)
            out = self._jfwd[r](self.params[r], x, tgt)
            self._saved[(r, mb)] = (x, tgt)
        else:
            out = self._jfwd[r](self.params[r], x)
            self._saved[(r, mb)] = (x,)
        out = jax.block_until_ready(out)
        t1 = time.perf_counter()
        self._cpu_busy += time.process_time() - c0
        self._spans.append((t0, t1))
        self._trace("fwd", t0, t1, r, mb)
        if last:
            # the driver reads the microbatch loss straight off this
            # call's ObjectRef — no separate loss plumbing
            return float(out)
        return np.asarray(out)

    def backward(self, r: int, mb: int, grad=None):
        """Backward one microbatch through virtual stage r*S + stage:
        grad is the next virtual stage's activation cotangent (None on
        the last, which seeds with 1/M so the accumulated grads are
        those of the MEAN loss). Returns the cotangent for the previous
        virtual stage (True from virtual stage 0)."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        c0 = time.process_time()
        v = r * self.num_stages + self.stage
        saved = self._saved.pop((r, mb))
        if grad is None:
            seed = jnp.float32(1.0 / self.num_microbatches)
        else:
            seed = self._put_batch(grad)
        tgt = saved[1] if len(saved) > 1 else None
        dparams, dx = self._jbwd[r](self.params[r], saved[0], tgt, seed)
        dparams = jax.block_until_ready(dparams)
        if self._grads[r] is None:
            self._grads[r] = dparams
        else:
            # accumulate in the resident layout — reduce-scattered 1/D
            # when zero_stage >= 2: this buffer IS the ZeRO-2 grad state
            self._grads[r] = jax.tree.map(jnp.add, self._grads[r],
                                          dparams)
        t1 = time.perf_counter()
        self._cpu_busy += time.process_time() - c0
        self._spans.append((t0, t1))
        self._trace("bwd", t0, t1, r, mb)
        if v == 0:
            return True
        return np.asarray(dx)

    def finish_step(self) -> dict:
        """Apply the accumulated grads per chunk (SGD(+momentum),
        matching `pipelined_train_step` at momentum=0) and report this
        stage's timing — busy seconds and the step window (the driver's
        bubble inputs) — plus the per-device resident bytes of each
        state component, measured at the point the grad state is fully
        accumulated (the honest ZeRO-2 number)."""
        import jax

        from ray_tpu.train.spmd import optimizer_state_bytes

        if self._saved:
            raise RuntimeError(
                f"stage {self.stage}: {len(self._saved)} microbatches "
                f"never ran backward — schedule bug")
        self._last_state_bytes = {
            "param_state_bytes": optimizer_state_bytes(self.params),
            "grad_state_bytes": optimizer_state_bytes(self._grads),
            "velocity_state_bytes": optimizer_state_bytes(self._vel),
        }
        for r in range(self.num_repeats):
            if self._grads[r] is None:
                continue
            vel = self._vel[r] if self._vel is not None else None
            self.params[r], new_vel = self._jupd(
                self.params[r], self._grads[r], vel)
            if self._vel is not None:
                self._vel[r] = new_vel
            self._grads[r] = None
        spans, self._spans = self._spans, []
        busy, self._cpu_busy = self._cpu_busy, 0.0
        busy_wall = sum(t1 - t0 for t0, t1 in spans)
        window = ((min(t0 for t0, _ in spans),
                   max(t1 for _, t1 in spans)) if spans else (0.0, 0.0))
        return {"stage": self.stage, "busy_s": busy,
                "busy_wall_s": busy_wall,
                "window_s": window[1] - window[0], "ops": len(spans),
                **self._last_state_bytes}

    def get_params(self) -> bytes:
        """This worker's current chunk params (numpy trees, list over
        repeats) — checkpoint shards and the parity tests' merge
        path."""
        import jax

        return cloudpickle.dumps(
            [jax.tree.map(np.asarray, c) for c in self.params])

    def ping(self) -> str:
        return "pong"

    def _trace(self, kind: str, t0: float, t1: float, r: int,
               mb: int) -> None:
        from ray_tpu.util import tracing

        v = r * self.num_stages + self.stage
        tracing.record_interval(
            f"pipeline.stage{self.stage}.v{v}.{kind}.mb{mb}", t0, t1,
            category="train")


@dataclasses.dataclass
class PipelineStepMetrics:
    loss: float
    bubble_ratio: float
    bubble_theoretical: float
    step_seconds: float
    microbatches: int
    virtual_stages: int = 0
    num_repeats: int = 1

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PipelineStrategy:
    """Drive 1F1B pipeline-parallel training of the pipelined
    transformer over `num_stages` stage workers — optionally interleaved
    (`num_repeats=R` virtual stages per worker) and/or composed with
    intra-stage ZeRO data parallelism (`zero_stage`, `data_parallel`).

    ::

        ps = PipelineStrategy(PipelinedConfig(), num_stages=2,
                              num_microbatches=8, num_repeats=2,
                              zero_stage=3, data_parallel=2)
        for _ in range(steps):
            metrics = ps.train_step({"tokens": ..., "targets": ...})
        ps.shutdown()
    """

    def __init__(self, cfg, num_stages: int,
                 num_microbatches: int | None = None, lr: float = 1e-2,
                 seed: int = 0, params=None,
                 resources_per_worker: dict | None = None,
                 placement_strategy: str = "PACK",
                 num_repeats: int = 1, zero_stage: int = 0,
                 data_parallel: int = 1, momentum: float = 0.0):
        import jax

        from ray_tpu.models.pipelined import (
            PipelinedConfig,
            init_pipelined,
            split_pipeline_stages_interleaved,
        )
        from ray_tpu.train.worker_group import WorkerGroup

        self.cfg = (cfg if isinstance(cfg, PipelinedConfig)
                    else PipelinedConfig(**dict(cfg or {})))
        self.num_stages = num_stages
        self.num_repeats = int(num_repeats)
        self.zero_stage = int(zero_stage)
        self.data_parallel = int(data_parallel)
        self.momentum = float(momentum)
        self.num_microbatches = int(
            num_microbatches or self.cfg.num_microbatches)
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0|1|2|3, "
                             f"got {zero_stage}")
        if self.num_repeats > 1 and self.num_microbatches < num_stages:
            raise ValueError(
                f"interleaved schedule needs microbatches "
                f"{self.num_microbatches} >= stages {num_stages}")
        self.lr = lr
        # FIFO workers: the schedule submission order must BE the
        # per-stage execution order (see module docstring)
        self.wg = WorkerGroup(
            num_workers=num_stages,
            resources_per_worker=resources_per_worker,
            placement_strategy=placement_strategy,
            worker_cls=PipelineStageWorker,
            max_concurrency=1,
        )
        try:
            # A stage that claims no TPU is kept on the CPU by the
            # runtime and needs virtual devices for its data-parallel
            # group; one that claims chips has them. Decided from the
            # request, never by asking jax here: a driver that
            # initializes a backend on a TPU host takes the chips its
            # stages need.
            if not (resources_per_worker or {}).get("TPU") and \
                    self.data_parallel > 1:
                ok = self.wg.execute("ensure_cpu_devices",
                                     self.data_parallel)
                if not all(ok):
                    raise RuntimeError(
                        "stage workers could not provision "
                        f"{self.data_parallel} cpu devices")
            if params is None:
                params = init_pipelined(jax.random.PRNGKey(seed),
                                        self.cfg)
            cfg_kwargs = dataclasses.asdict(self.cfg)
            stages = split_pipeline_stages_interleaved(
                params, self.cfg, num_stages, self.num_repeats)
            self.stage_param_counts = [
                self.wg.execute_single(
                    s, "load_stage", cfg_kwargs,
                    cloudpickle.dumps(
                        [jax.tree.map(np.asarray, c) for c in stages[s]]),
                    lr, self.num_microbatches, self.num_repeats,
                    self.zero_stage, self.data_parallel, self.momentum)
                for s in range(num_stages)
            ]
        except Exception:
            self.wg.shutdown()
            raise
        self.last_metrics: PipelineStepMetrics | None = None
        self.last_stage_stats: list[dict] | None = None

    # ------------------------------------------------------------------

    def train_step(self, batch: dict) -> dict:
        """One pipelined step over the whole batch: split into M
        microbatches, stream activations down / cotangents up the
        virtual-stage chain (flat or interleaved order), then apply
        each stage's update. Returns {loss, bubble_ratio,
        bubble_theoretical, step_seconds, microbatches, virtual_stages,
        num_repeats}."""
        import ray_tpu
        from ray_tpu.util import tracing

        S, M, R = self.num_stages, self.num_microbatches, self.num_repeats
        V = S * R
        tokens = np.asarray(batch["tokens"])
        targets = np.asarray(batch["targets"])
        B = tokens.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by "
                             f"microbatches {M}")
        mb = B // M
        order = (interleaved_1f1b_submission_order(S, M, R) if R > 1
                 else one_f_one_b_submission_order(S, M))
        t0 = time.perf_counter()
        with tracing.span("pipeline.train_step", category="train"):
            fwd: dict[tuple[int, int], Any] = {}
            bwd: dict[tuple[int, int], Any] = {}
            for kind, v, m in order:
                w = self.wg.workers[v % S]
                r = v // S
                if kind == "fwd":
                    payload = (tokens[m * mb:(m + 1) * mb] if v == 0
                               else fwd[(v - 1, m)])
                    tgt = (targets[m * mb:(m + 1) * mb]
                           if v == V - 1 else None)
                    fwd[(v, m)] = w.forward.remote(r, m, payload, tgt)
                else:
                    g = bwd[(v + 1, m)] if v < V - 1 else None
                    bwd[(v, m)] = w.backward.remote(r, m, g)
            losses = ray_tpu.get([fwd[(V - 1, m)] for m in range(M)],
                                 timeout=300)
            ray_tpu.get([bwd[(0, m)] for m in range(M)], timeout=300)
            stats = self.wg.execute("finish_step")
        dt = time.perf_counter() - t0
        makespan = max(st["window_s"] for st in stats)
        busy = sum(st["busy_s"] for st in stats)
        bubble = (1.0 - busy / (S * makespan)) if makespan > 0 else 0.0
        m_bubble, m_micro, m_virtual = _strategy_metrics()
        m_bubble.set(bubble)
        m_micro.inc(M)
        m_virtual.set(float(V))
        self.last_stage_stats = stats
        self.last_metrics = PipelineStepMetrics(
            loss=float(np.mean(losses)),
            bubble_ratio=bubble,
            bubble_theoretical=(
                theoretical_bubble_interleaved(S, M, R) if R > 1
                else theoretical_bubble(S, M)),
            step_seconds=dt,
            microbatches=M,
            virtual_stages=V,
            num_repeats=R,
        )
        return self.last_metrics.as_dict()

    def full_params(self):
        """Merge every worker's current chunk params back into one tree
        (the single-program layout) — checkpoint/parity surface."""
        from ray_tpu.models.pipelined import (
            merge_pipeline_stages_interleaved,
        )

        blobs = self.wg.execute("get_params")
        return merge_pipeline_stages_interleaved(
            [cloudpickle.loads(b) for b in blobs])

    # ------------------------------------------------------------------

    def save_checkpoint(self, directory: str):
        """Write a restore-compatible checkpoint: every stage worker
        reports its param shard (`get_params`), the driver persists one
        shard file per stage plus a meta manifest. Pair with
        `load_pipeline_checkpoint` (reassembles the full single-program
        tree) and `CheckpointManager.register` for retention."""
        from ray_tpu.train.checkpoint import Checkpoint

        os.makedirs(directory, exist_ok=True)
        blobs = self.wg.execute("get_params")
        for s, blob in enumerate(blobs):
            with open(os.path.join(directory, f"stage_{s:04d}.pkl"),
                      "wb") as f:
                f.write(blob)
        meta = {
            "format": "pipeline-stage-shards-v1",
            "num_stages": self.num_stages,
            "num_repeats": self.num_repeats,
            "zero_stage": self.zero_stage,
            "data_parallel": self.data_parallel,
            "model": dataclasses.asdict(self.cfg),
        }
        with open(os.path.join(directory, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1)
        return Checkpoint(directory)

    def shutdown(self):
        self.wg.shutdown()


def load_pipeline_checkpoint(path: str):
    """Reassemble a `PipelineStrategy.save_checkpoint` directory into
    (full_params, meta): per-stage shard files merge back into the
    single-program param tree, restore-compatible with both
    `PipelineStrategy(params=...)` (any stage/repeat split) and the
    in-program `pipelined_train_step`."""
    from ray_tpu.models.pipelined import merge_pipeline_stages_interleaved

    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    chunks = []
    for s in range(int(meta["num_stages"])):
        with open(os.path.join(path, f"stage_{s:04d}.pkl"), "rb") as f:
            chunks.append(cloudpickle.loads(f.read()))
    return merge_pipeline_stages_interleaved(chunks), meta
