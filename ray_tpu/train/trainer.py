"""JaxTrainer — distributed data/model-parallel training driver.

Reference parity: TorchTrainer/DataParallelTrainer + BackendExecutor
(train/torch/torch_trainer.py:11, train/data_parallel_trainer.py:25,
train/_internal/backend_executor.py:69,142,458) with the v2 controller's
failure handling (train/v2/_internal/execution/controller.py:73) — no
Tune coupling in the fit path (the v2 design).

Flow: fit() creates a WorkerGroup of actors gang-placed in a PG, wires
rank/world env + the jax.distributed rendezvous (rank 0 hosts the
coordinator), starts the user train loop on every worker, then drives
the result loop — registering reported checkpoints (top-k) and
restarting the whole gang from the latest checkpoint on worker failure.
Gang-level restart is deliberate: one SPMD program spans all hosts, so a
single lost process invalidates the whole world (SURVEY.md §7 hard
parts) — elasticity is at gang granularity, unlike per-worker NCCL
rebuilds."""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable

import cloudpickle

from ray_tpu.train.checkpoint import (
    Checkpoint,
    CheckpointConfig,
    CheckpointManager,
)
from ray_tpu.train.worker_group import WorkerGroup, WorkerGroupError


@dataclasses.dataclass
class ScalingConfig:
    """Reference: ray.train.ScalingConfig (air/config.py). num_workers is
    the number of jax PROCESSES (one per host on TPU), not chips. Setting
    min_workers turns on ELASTIC sizing (reference: Train v2
    ScalingPolicy, v2/_internal/execution/scaling_policy/scaling_policy.py:26):
    each gang (re)start sizes the world to what the cluster can place,
    within [min_workers, num_workers]."""

    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: dict[str, float] | None = None
    placement_strategy: str = "PACK"
    # jax-on-CPU workers: how many virtual devices each process exposes
    # (tests / laptops; None on real TPU workers)
    num_cpu_devices_per_worker: int | None = None
    min_workers: int | None = None  # elastic floor (None = fixed size)
    # mid-run elastic: how often the result loop re-evaluates the
    # scaling decision against live capacity (reference: Train v2's
    # continuous ScalingPolicy, scaling_policy.py:26). 0 disables —
    # sizing then happens only at gang (re)starts.
    elastic_interval_s: float = 0.0

    def decide_num_workers(self) -> int:
        """Elastic sizing decision against the live resource view."""
        if self.min_workers is None:
            return self.num_workers
        import ray_tpu

        avail = ray_tpu.available_resources()
        req = self.worker_resources()
        fit = self.num_workers
        for r, q in req.items():
            if q > 0:
                # epsilon guards float residue from fractional releases
                fit = min(fit, int((avail.get(r, 0.0) + 1e-9) // q))
        return max(self.min_workers, min(self.num_workers, fit))

    def extra_capacity(self) -> int:
        """How many MORE workers the cluster could place right now (the
        running gang's own resources are already subtracted from the
        availability view)."""
        import ray_tpu

        avail = ray_tpu.available_resources()
        fit = 1 << 30
        for r, q in self.worker_resources().items():
            if q > 0:
                fit = min(fit, int((avail.get(r, 0.0) + 1e-9) // q))
        return max(0, fit)

    def worker_resources(self) -> dict[str, float]:
        if self.resources_per_worker is not None:
            return dict(self.resources_per_worker)
        return {"CPU": 1.0, "TPU": 1.0} if self.use_tpu else {"CPU": 1.0}


@dataclasses.dataclass
class FailureConfig:
    """Reference: ray.train.FailureConfig — max_failures gang restarts."""

    max_failures: int = 0


@dataclasses.dataclass
class RunConfig:
    """Reference: ray.train.RunConfig (air/config.py)."""

    name: str | None = None
    storage_path: str | None = None
    failure_config: FailureConfig | None = None
    checkpoint_config: CheckpointConfig | None = None
    # Tune stop criteria: {"metric": threshold} — a trial terminates when
    # any named metric reaches its threshold (reference: air/config.py
    # RunConfig.stop)
    stop: dict | None = None


@dataclasses.dataclass
class Result:
    """Reference: ray.train.Result."""

    metrics: dict
    checkpoint: Checkpoint | None
    path: str
    error: BaseException | None = None
    metrics_history: list = dataclasses.field(default_factory=list)


class TrainingFailedError(RuntimeError):
    pass


class JaxTrainer:
    """Run `train_loop_per_worker` on a gang of workers.

    The loop uses the session API (ray_tpu.train.report /
    get_context / get_checkpoint); inside it, build a mesh over
    jax.devices() — jax.distributed is already initialized across the
    gang by the time the loop runs."""

    def __init__(
        self,
        train_loop_per_worker: Callable | None = None,
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
        resume_from_checkpoint: Checkpoint | None = None,
        datasets: dict | None = None,
        strategy: str = "spmd",
    ):
        if strategy not in ("spmd", "pipeline"):
            raise ValueError(f"unknown train strategy {strategy!r} "
                             "(spmd | pipeline)")
        if strategy == "spmd" and train_loop_per_worker is None:
            raise ValueError("spmd strategy needs train_loop_per_worker")
        self._fn = train_loop_per_worker
        self._config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self._resume = resume_from_checkpoint
        self.strategy = strategy
        # name -> ray_tpu.data.Dataset, split across the gang at start
        # (reference: DataParallelTrainer datasets= + get_dataset_shard)
        self._datasets = datasets or {}

    # ------------------------------------------------------------------

    def fit(self) -> Result:
        if self.strategy == "pipeline":
            return self._fit_pipeline()
        return self._fit_spmd()

    def _fit_pipeline(self) -> Result:
        """Pipeline-parallel fit: stages on worker subsets, the 1F1B
        schedule per step (train/pipeline_strategy.py). Config keys in
        train_loop_config: `model` (PipelinedConfig kwargs), `batch`
        ({tokens, targets} numpy), `steps`, `num_stages` (default:
        scaling_config.num_workers), `num_microbatches`, `lr`, `seed`,
        plus the interleaved/ZeRO composition knobs `num_repeats`,
        `zero_stage`, `data_parallel`, `momentum`. Stage workers
        checkpoint their param shards through the CheckpointManager
        every `checkpoint_frequency` steps (the manager reassembles a
        restore-compatible full state via
        `load_pipeline_checkpoint`)."""
        from ray_tpu.train.pipeline_strategy import PipelineStrategy

        cfg = dict(self._config or {})
        if "batch" not in cfg:
            raise ValueError("pipeline strategy needs "
                             "train_loop_config['batch']")
        name = self.run_config.name or f"pipeline_{int(time.time())}"
        storage = self.run_config.storage_path or os.path.join(
            os.path.expanduser("~"), "ray_tpu_results")
        exp_dir = os.path.join(storage, name)
        os.makedirs(exp_dir, exist_ok=True)
        ckpt_cfg = (self.run_config.checkpoint_config
                    or CheckpointConfig())
        manager = CheckpointManager(exp_dir, ckpt_cfg)
        sc = self.scaling_config
        ps = PipelineStrategy(
            cfg.get("model") or {},
            num_stages=cfg.get("num_stages", sc.num_workers),
            num_microbatches=cfg.get("num_microbatches"),
            lr=cfg.get("lr", 1e-2),
            seed=cfg.get("seed", 0),
            resources_per_worker=sc.resources_per_worker,
            placement_strategy=sc.placement_strategy,
            num_repeats=int(cfg.get("num_repeats", 1)),
            zero_stage=int(cfg.get("zero_stage", 0)),
            data_parallel=int(cfg.get("data_parallel", 1)),
            momentum=float(cfg.get("momentum", 0.0)),
        )
        from ray_tpu import dashboard as _dash

        history: list[dict] = []
        last_ckpt: Checkpoint | None = None
        try:
            steps = int(cfg.get("steps", 1))
            freq = max(1, int(ckpt_cfg.checkpoint_frequency or 1))
            for step in range(steps):
                metrics = ps.train_step(cfg["batch"])
                metrics["step"] = step
                history.append(metrics)
                if (step + 1) % freq == 0 or step == steps - 1:
                    staged = ps.save_checkpoint(
                        os.path.join(exp_dir, f"staging_{step:06d}"))
                    last_ckpt = manager.register(staged, metrics)
                _dash.publish_view("train", name, {
                    "status": "RUNNING", "iteration": len(history),
                    "num_workers": ps.num_stages, "metrics": metrics})
            _dash.publish_view("train", name, {
                "status": "FINISHED", "iteration": len(history),
                "num_workers": ps.num_stages,
                "metrics": history[-1] if history else {}})
        except BaseException as e:
            # terminal-status contract matches the spmd path: a dead
            # view must not read RUNNING forever
            _dash.publish_view("train", name, {
                "status": "FAILED", "iteration": len(history),
                "error": str(e)})
            raise
        finally:
            ps.shutdown()
        return Result(metrics=history[-1] if history else {},
                      checkpoint=last_ckpt, path=exp_dir,
                      metrics_history=history)

    def _fit_spmd(self) -> Result:
        name = self.run_config.name or f"jax_trainer_{int(time.time())}"
        storage = self.run_config.storage_path or os.path.join(
            os.path.expanduser("~"), "ray_tpu_results")
        exp_dir = os.path.join(storage, name)
        os.makedirs(exp_dir, exist_ok=True)
        manager = CheckpointManager(
            exp_dir, self.run_config.checkpoint_config or CheckpointConfig())
        failure_config = self.run_config.failure_config or FailureConfig()

        resume = self._resume or manager.latest()
        resize_to = None
        failures = 0
        history: list[dict] = []
        last_error: BaseException | None = None
        from ray_tpu import dashboard as _dash

        _dash.publish_view("train", name, {
            "status": "RUNNING", "iteration": 0,
            "num_workers": self.scaling_config.num_workers})
        while True:
            wg = None
            try:
                target, resize_to = resize_to, None  # one-shot: a FAILED
                # resized start must not retry the stale target forever
                wg = self._start_worker_group(name, exp_dir, resume, target)
                metrics, ckpt = self._result_loop(wg, manager, history,
                                                  run_name=name)
                _dash.publish_view("train", name, {
                    "status": "FINISHED", "iteration": len(history),
                    "num_workers": wg.num_workers, "metrics": metrics})
                return Result(metrics=metrics, checkpoint=ckpt or
                              manager.latest(), path=exp_dir,
                              metrics_history=history)
            except _ElasticResize as e:
                # mid-run scaling decision: controlled gang restart from
                # the latest checkpoint at a result boundary (does not
                # consume the failure budget — reference: Train v2
                # ScalingPolicy resize decisions, scaling_policy.py:26).
                # The TARGET rides along: the availability view right
                # after shutdown is stale (old workers still releasing),
                # so re-deciding from it would undo the resize.
                resume = manager.latest()
                resize_to = e.target
            except (WorkerGroupError, _WorkerFailure) as e:
                last_error = e
                failures += 1
                if failures > failure_config.max_failures:
                    _dash.publish_view("train", name, {
                        "status": "FAILED", "iteration": len(history),
                        "error": str(e)})
                    raise TrainingFailedError(
                        f"training failed after {failures - 1} restarts: {e}"
                    ) from e
                resume = manager.latest()  # gang restart from latest ckpt
            finally:
                if wg is not None:
                    wg.shutdown()

    # ------------------------------------------------------------------

    def _start_worker_group(self, name: str, exp_dir: str,
                            resume: Checkpoint | None,
                            num_override: int | None = None) -> WorkerGroup:
        sc = self.scaling_config
        n_workers = num_override or sc.decide_num_workers()
        wg = WorkerGroup(
            num_workers=n_workers,
            resources_per_worker=sc.worker_resources(),
            placement_strategy=sc.placement_strategy,
        )
        try:
            infos = wg.execute("node_info")
            coordinator = None
            if wg.num_workers > 1:
                coordinator = f"{infos[0]['ip']}:{infos[0]['port']}"
            # rank/world env (reference: _create_rank_world_size_mappings,
            # backend_executor.py:376) + local ranks grouped by node
            by_node: dict[str, list[int]] = {}
            for rank, info in enumerate(infos):
                by_node.setdefault(info["node_id"], []).append(rank)
            node_order = list(by_node)
            # slice-identity view: node labels + per-node IPs, for the
            # slice-derived topology env (reference: backend_executor.py
            # :306-322 shares the slice view across colocated workers)
            node_labels: dict[str, dict] = {}
            node_ip_by_id: dict[str, str] = {}
            if sc.use_tpu:
                import ray_tpu as _rt

                try:
                    for n in _rt.nodes():
                        node_labels[n["NodeID"]] = n.get("Labels") or {}
                except Exception:  # local mode: no cluster view
                    pass
                for i in infos:
                    node_ip_by_id.setdefault(i["node_id"], i["ip"])
            env_refs = []
            for rank, info in enumerate(infos):
                node_id = info["node_id"]
                env = {
                    "RAY_TPU_TRAIN_RANK": rank,
                    "RAY_TPU_TRAIN_WORLD_SIZE": wg.num_workers,
                    "RAY_TPU_TRAIN_LOCAL_RANK": by_node[node_id].index(rank),
                    "RAY_TPU_TRAIN_NODE_RANK": node_order.index(node_id),
                }
                if sc.use_tpu:
                    # libtpu multi-host topology env (reference:
                    # TPUAcceleratorManager worker-id/hostnames wiring,
                    # _private/accelerators/tpu.py:157-170). Per HOST,
                    # not per worker: multiple train workers can share a
                    # TPU host. When the node carries slice labels, the
                    # worker id / hostnames come from SLICE identity
                    # (worker-id order), not gang join order.
                    from ray_tpu.core import tpu as tpu_mod

                    labels = node_labels.get(node_id, {})
                    env.update(self._slice_topology_env(
                        tpu_mod, labels, node_id, node_labels,
                        node_ip_by_id))
                if coordinator:
                    env["RAY_TPU_TRAIN_COORDINATOR"] = coordinator
                env_refs.append((rank, env))
            for rank, env in env_refs:
                wg.execute_single(rank, "setup_env", env)
            # jax.distributed rendezvous: all workers join concurrently
            # (initialize blocks until the world is complete)
            import ray_tpu

            refs = [
                getattr(w, "setup_jax").remote(
                    coordinator, wg.num_workers, rank,
                    sc.num_cpu_devices_per_worker, sc.use_tpu)
                for rank, w in enumerate(wg.workers)
            ]
            device_counts = ray_tpu.get(refs, timeout=180)
            # every rank must see the devices it was sized for: its
            # virtual CPU devices, or the chips its TPU claim bought
            want = sc.num_cpu_devices_per_worker or (
                int(sc.worker_resources().get("TPU", 0))
                if sc.use_tpu else 0)
            if want and any(n != want for n in device_counts):
                raise WorkerGroupError(
                    f"train workers see {device_counts} local devices, "
                    f"expected {want} each")
            fn_blob = cloudpickle.dumps(self._fn)
            for rank, info in enumerate(infos):
                node_id = info["node_id"]
                ctx = dict(
                    world_size=wg.num_workers,
                    world_rank=rank,
                    local_rank=by_node[node_id].index(rank),
                    local_world_size=len(by_node[node_id]),
                    node_rank=node_order.index(node_id),
                    experiment_name=name,
                    trial_dir=exp_dir,
                    coordinator_address=coordinator,
                )
                shards_blob = None
                if self._datasets:
                    shards_blob = cloudpickle.dumps({
                        dname: ds.shard(wg.num_workers, rank)
                        for dname, ds in self._datasets.items()})
                wg.execute_single(
                    rank, "start_training", fn_blob, self._config, ctx,
                    resume.path if resume else None, shards_blob)
            return wg
        except Exception as e:
            wg.shutdown()
            if isinstance(e, WorkerGroupError):
                raise
            raise WorkerGroupError(f"worker group bootstrap failed: {e}") \
                from e

    # ------------------------------------------------------------------

    @staticmethod
    def _slice_topology_env(tpu_mod, labels, node_id, node_labels,
                            node_ip_by_id):
        """TPU topology env for one worker. Slice-labelled nodes get their
        asserted TPU_WORKER_ID and hostnames ordered by worker-id across
        the gang's members of the same slice. A node without slice
        labels keeps the TPU environment its platform gave it: gang
        join order is not a topology, and on a single host libtpu needs
        no help."""
        sl = labels.get(tpu_mod.SLICE_LABEL)
        if sl is None or labels.get(tpu_mod.WORKER_ID_LABEL) is None:
            return {}
        members = sorted(
            ((int(lb[tpu_mod.WORKER_ID_LABEL]), nid)
             for nid, lb in node_labels.items()
             if lb.get(tpu_mod.SLICE_LABEL) == sl
             and lb.get(tpu_mod.WORKER_ID_LABEL) is not None
             and nid in node_ip_by_id))
        slice_ips = [node_ip_by_id[nid] for _, nid in members]
        # libtpu requires worker ids to index the hostname list 0..n-1.
        # A gang covering the FULL slice keeps the asserted ids; a gang on
        # a subset of hosts is reindexed by position (self-consistent
        # contiguous view of the sub-slice).
        position = next((i for i, (_, nid) in enumerate(members)
                         if nid == node_id), 0)
        return tpu_mod.topology_env(labels, slice_ips, worker_id=position)

    def _result_loop(self, wg: WorkerGroup, manager: CheckpointManager,
                     history: list, run_name: str = ""
                     ) -> tuple[dict, Checkpoint | None]:
        """Drive rounds of per-worker reports until every worker finishes
        (reference: backend_executor.get_next_results — all workers must
        report in lockstep)."""
        from ray_tpu.core import exceptions as exc

        last_metrics: dict = {}
        last_ckpt: Checkpoint | None = None
        finished: set[int] = set()
        sc = self.scaling_config
        next_elastic_check = (time.monotonic() + sc.elastic_interval_s
                              if sc.elastic_interval_s > 0 else None)
        while len(finished) < wg.num_workers:
            if next_elastic_check is not None and \
                    time.monotonic() >= next_elastic_check:
                next_elastic_check = time.monotonic() + sc.elastic_interval_s
                want = min(sc.num_workers,
                           wg.num_workers + sc.extra_capacity())
                if want > wg.num_workers and last_ckpt is not None:
                    # capacity appeared: grow the gang at a checkpointed
                    # boundary (shrink happens via the failure path when
                    # a worker is lost)
                    raise _ElasticResize(wg.num_workers, want)
            round_reports: dict[int, dict] = {}
            for rank in range(wg.num_workers):
                if rank in finished:
                    continue
                deadline = time.monotonic() + 300
                while True:
                    try:
                        r = wg.execute_single(rank, "next_result",
                                              timeout=30.0)
                    except exc.GetTimeoutError:
                        # slow (e.g. long XLA compile under load), not
                        # dead — keep polling until the round deadline
                        if time.monotonic() > deadline:
                            raise _WorkerFailure(
                                f"train worker {rank} unresponsive for "
                                f"300s", rank) from None
                        continue
                    except (exc.ActorDiedError, exc.ActorUnavailableError,
                            exc.TaskError) as e:
                        raise _WorkerFailure(
                            f"train worker {rank} died: {e}", rank) from e
                    if r["status"] == "report":
                        round_reports[rank] = r
                        break
                    if r["status"] == "finished":
                        finished.add(rank)
                        break
                    if r["status"] == "error":
                        raise _WorkerFailure(
                            f"train loop failed on rank {rank}: "
                            f"{r['error']}\n{r.get('traceback', '')}", rank)
                    if time.monotonic() > deadline:
                        raise _WorkerFailure(
                            f"train worker {rank} produced no result in "
                            f"300s", rank)
            if round_reports:
                rank0 = round_reports.get(0)
                if rank0 is not None:
                    last_metrics = rank0["metrics"]
                    history.append(dict(last_metrics))
                    if rank0.get("checkpoint_dir"):
                        last_ckpt = manager.register(
                            Checkpoint(rank0["checkpoint_dir"]),
                            last_metrics)
                    if run_name:
                        from ray_tpu import dashboard as _dash

                        _dash.publish_view("train", run_name, {
                            "status": "RUNNING",
                            "iteration": len(history),
                            "num_workers": wg.num_workers,
                            "metrics": last_metrics})
        return last_metrics, last_ckpt


class _WorkerFailure(RuntimeError):
    def __init__(self, msg, rank):
        super().__init__(msg)
        self.rank = rank


class _ElasticResize(Exception):
    def __init__(self, current: int, target: int):
        super().__init__(f"elastic resize {current} -> {target}")
        self.current = current
        self.target = target
