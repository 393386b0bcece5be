"""WorkerGroup — the gang of train-worker actors.

Reference parity: ray.train._internal.worker_group.WorkerGroup
(worker_group.py:102) + the actor-side _RayTrainWorker. Workers are
actors placed in one placement group (gang semantics: all-or-nothing,
strategy-shaped — backend_executor.py:142); each runs the user train
function on a dedicated thread with a TrainSession and serves
result-polling calls.

TPU-first: one worker per HOST (a worker owns every chip the nodelet
granted it), not one per device — a pod slice runs ONE SPMD program
(SURVEY.md §7), so world_size == number of jax processes.
"""

from __future__ import annotations

import os
import socket
import threading
import traceback
from typing import Any

import cloudpickle


class TrainWorker:
    """Actor hosted in a worker process. One per train rank."""

    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self.session = None

    # -- rendezvous ------------------------------------------------------

    def node_info(self) -> dict:
        """IP + a free port (rank 0's becomes the jax.distributed
        coordinator — reference rendezvous: train/torch/config.py:156 via
        get_address_and_port)."""
        from ray_tpu.core.rpc import node_ip

        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        import ray_tpu

        return {"ip": node_ip(), "port": port,
                "node_id": ray_tpu.get_runtime_context().node_id.hex()}

    def setup_env(self, env: dict) -> bool:
        os.environ.update({k: str(v) for k, v in env.items()})
        return True

    def setup_jax(self, coordinator: str | None, num_processes: int,
                  process_id: int, num_cpu_devices: int | None,
                  use_tpu: bool = False) -> int:
        """Configure jax in this process and join the distributed system
        (reference seam: Backend.on_start — _TorchBackend runs
        dist.init_process_group here, train/torch/config.py:66-124; the
        jax-native equivalent is jax.distributed.initialize with rank-0's
        address). Returns this process's local device count."""
        import jax

        if num_cpu_devices:
            # an inherited --xla_force_host_platform_device_count (e.g.
            # from a test driver) would override jax_num_cpu_devices.
            # The backend initializes lazily at the first device query
            # below, so editing XLA_FLAGS after the import is in time.
            flags = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = " ".join(
                f for f in flags.split()
                if "--xla_force_host_platform_device_count" not in f)
            jax.config.update("jax_num_cpu_devices", int(num_cpu_devices))
            jax.config.update("jax_platforms", "cpu")
        if coordinator and num_processes > 1:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
        if use_tpu and not num_cpu_devices and \
                jax.default_backend() != "tpu":
            raise RuntimeError(
                f"use_tpu=True but this worker's jax backend is "
                f"{jax.default_backend()!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}, TPU_VISIBLE_CHIPS="
                f"{os.environ.get('TPU_VISIBLE_CHIPS')!r})")
        return len(jax.local_devices())

    # -- training --------------------------------------------------------

    def start_training(self, fn_blob: bytes, train_loop_config: dict | None,
                       ctx: dict, resume_dir: str | None,
                       dataset_shards_blob: bytes | None = None) -> bool:
        from ray_tpu.train import session as S
        from ray_tpu.train.checkpoint import Checkpoint

        fn = cloudpickle.loads(fn_blob)
        shards = (cloudpickle.loads(dataset_shards_blob)
                  if dataset_shards_blob else None)
        context = S.TrainContext(**ctx)
        resume = Checkpoint(resume_dir) if resume_dir else None
        self.session = S.init_session(context, resume, shards)

        def run():
            try:
                if train_loop_config is not None:
                    result = fn(train_loop_config)
                else:
                    result = fn()
                self.session.final = result
            except BaseException as e:  # noqa: BLE001
                self.session.error = e
                self.session.error_tb = traceback.format_exc()
            finally:
                self.session.finished.set()

        threading.Thread(target=run, daemon=True,
                         name=f"train-fn-rank{self.rank}").start()
        return True

    def set_step_waterfall(self, on: bool = True) -> bool:
        """Flip per-step latency attribution in this worker process
        (train/spmd.py waterfall) — works after spmd is imported, unlike
        the RAY_TPU_STEP_WATERFALL env var which is read at import."""
        os.environ["RAY_TPU_STEP_WATERFALL"] = "1" if on else ""
        from ray_tpu.train import spmd

        spmd.enable_step_waterfall(on)
        return True

    def step_waterfall_summary(self) -> dict:
        """This rank's accumulated per-step phase attribution."""
        from ray_tpu.train import spmd

        return spmd.waterfall.summary()

    def next_result(self, timeout: float = 5.0) -> dict:
        """One report from this worker's session, or a status sentinel.
        Driven by the driver's result loop (reference:
        backend_executor.get_next_results :585)."""
        s = self.session
        if s is None:
            return {"status": "idle"}
        r = s.next_result(timeout=timeout)
        if r is not None:
            return {"status": "report", **r}
        if s.finished.is_set():
            if s.error is not None:
                return {"status": "error", "error": repr(s.error),
                        "traceback": getattr(s, "error_tb", "")}
            return {"status": "finished", "final": _safe(s.final)}
        return {"status": "running"}

    def ping(self) -> str:
        return "pong"


def _safe(v):
    try:
        cloudpickle.dumps(v)
        return v
    except Exception:  # noqa: BLE001
        return repr(v)


class WorkerGroupError(RuntimeError):
    def __init__(self, msg, rank=None):
        super().__init__(msg)
        self.rank = rank


class WorkerGroup:
    """N worker actors in one placement group.

    The default worker class is `TrainWorker` (SPMD gangs); strategies
    that need a different actor shape pass `worker_cls` — any class
    whose __init__ is (rank, world_size). The pipeline strategy
    (train/pipeline_strategy.py) runs its stage workers FIFO
    (`max_concurrency=1`) so the driver's 1F1B submission order is the
    per-stage execution order."""

    def __init__(self, num_workers: int,
                 resources_per_worker: dict[str, float] | None = None,
                 placement_strategy: str = "PACK",
                 pg_timeout: float = 60.0,
                 worker_cls: type | None = None,
                 max_concurrency: int = 2):
        import ray_tpu
        from ray_tpu.util.placement_group import (
            placement_group,
            remove_placement_group,
        )

        self.num_workers = num_workers
        res = dict(resources_per_worker or {"CPU": 1.0})
        self._remove_pg = remove_placement_group
        self.pg = placement_group([dict(res) for _ in range(num_workers)],
                                  strategy=placement_strategy)
        if not self.pg.wait(pg_timeout):
            self._remove_pg(self.pg)
            raise WorkerGroupError(
                f"placement group for {num_workers} x {res} not placeable "
                f"within {pg_timeout}s")
        from ray_tpu import accelerators

        # the actor claims its bundle's accelerators: that claim is what
        # makes the nodelet hand those chips to the worker's process
        claimed = {k: v for k, v in res.items()
                   if k in accelerators.all_managers()}
        cls = ray_tpu.remote(num_cpus=0, resources=claimed)(
            worker_cls or TrainWorker)
        self.workers = [
            cls.options(
                placement_group=self.pg,
                placement_group_bundle_index=i,
                # default 2: next_result poll + control calls
                max_concurrency=max_concurrency,
            ).remote(i, num_workers)
            for i in range(num_workers)
        ]

    def execute(self, method: str, *args, timeout: float | None = 120.0,
                **kwargs) -> list:
        import ray_tpu
        from ray_tpu.util import tracing

        # one span per gang call: every rank's actor-side span carries a
        # child of this context, so the merged timeline shows the whole
        # gang under one trace_id (straggler ranks stick out)
        with tracing.span(f"worker_group.{method}", category="train"):
            refs = [getattr(w, method).remote(*args, **kwargs)
                    for w in self.workers]
            return ray_tpu.get(refs, timeout=timeout)

    def execute_single(self, rank: int, method: str, *args,
                       timeout: float | None = 120.0, **kwargs) -> Any:
        import ray_tpu
        from ray_tpu.util import tracing

        with tracing.span(f"worker_group.{method}[{rank}]",
                          category="train"):
            ref = getattr(self.workers[rank], method).remote(*args,
                                                             **kwargs)
            return ray_tpu.get(ref, timeout=timeout)

    def enable_step_waterfall(self, on: bool = True) -> list:
        """Flip per-step attribution on EVERY rank; fetch the per-rank
        phase tables afterwards with
        ``execute("step_waterfall_summary")`` (straggler ranks show up
        as one rank's compute/collective share diverging)."""
        return self.execute("set_step_waterfall", on)

    def execute_async(self, method: str, *args, **kwargs) -> list:
        from ray_tpu.util import tracing

        with tracing.span(f"worker_group.{method}.submit",
                          category="train"):
            return [getattr(w, method).remote(*args, **kwargs)
                    for w in self.workers]

    def shutdown(self):
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        try:
            self._remove_pg(self.pg)
        except Exception:  # noqa: BLE001
            pass
        self.workers = []
