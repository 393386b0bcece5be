"""ray_tpu — a TPU-native distributed computing framework.

A ground-up rebuild of the capabilities of Ray (tasks, actors, objects,
placement groups, distributed scheduling, fault tolerance) plus its ML
libraries (Train, Tune, RLlib, Data, Serve), designed TPU-first:

- compute is expressed as SPMD programs over ``jax.sharding.Mesh`` device
  meshes; collectives lower to XLA ICI/DCN primitives (psum, all_gather,
  ppermute, all_to_all) instead of NCCL worlds,
- the scheduler understands TPU pod-slice topology as a first-class
  resource (slice bundles, host gang scheduling),
- hot ops (attention, collectives overlap) are pallas TPU kernels.

Public core API (reference parity: python/ray/_private/worker.py:1275,
python/ray/remote_function.py:41, python/ray/actor.py:602):

    import ray_tpu as ray
    ray.init()
    @ray.remote
    def f(x): return x + 1
    ref = f.remote(1)
    ray.get(ref)
"""

from ray_tpu import _compile_cache
from ray_tpu._version import __version__
from ray_tpu.core.api import (
    ObjectRef,
    ObjectRefGenerator,
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    get_runtime_context,
    init,
    timeline,
    is_initialized,
    kill,
    method,
    nodes,
    put,
    remote,
    shutdown,
    wait,
)

_compile_cache.configure()

__all__ = [
    "__version__",
    "ObjectRef",
    "ObjectRefGenerator",
    "available_resources",
    "cancel",
    "cluster_resources",
    "get",
    "get_actor",
    "get_runtime_context",
    "init",
    "timeline",
    "is_initialized",
    "kill",
    "method",
    "nodes",
    "put",
    "remote",
    "shutdown",
    "wait",
]
