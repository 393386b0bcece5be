"""Accelerator plugin registry (reference: ray._private.accelerators —
AcceleratorManager ABC + per-type registry): TPU detection from a faked
device tree, and k-of-n chip visibility for the workers a nodelet
spawns."""

import os
import sys

import cloudpickle
import pytest

from ray_tpu import accelerators as acc
from ray_tpu.core.nodelet import _aligned_block

# workers unpickle the remote functions below without importing this file
cloudpickle.register_pickle_by_value(sys.modules[__name__])

TPU = acc.TPUAcceleratorManager


def test_registry_has_tpu_and_gpu():
    managers = acc.all_managers()
    assert managers["TPU"] is TPU
    assert managers["GPU"] is acc.NvidiaGPUAcceleratorManager
    assert acc.get_manager("TPU").resource_name == "TPU"
    assert acc.get_manager("nope") is None


def _touch(path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    open(path, "w").close()


def test_detects_chips_from_device_tree(tmp_path, monkeypatch):
    """One /dev/accelN per chip, or one numbered vfio group per chip
    (the v5e hosts expose /dev/vfio/0..3 + the /dev/vfio/vfio container
    node). Environment variables describing a topology count nothing."""
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    root = str(tmp_path)
    assert TPU.get_current_node_num_accelerators(root) == 0
    assert TPU.get_current_node_num_accelerators(
        str(tmp_path / "missing")) == 0
    for i in range(4):
        _touch(f"{root}/vfio/{i}")
    _touch(f"{root}/vfio/vfio")
    assert TPU.get_current_node_num_accelerators(root) == 4
    # accel nodes win where the driver exposes them
    _touch(f"{root}/accel0")
    _touch(f"{root}/accel1")
    _touch(f"{root}/accelerometer")
    assert TPU.get_current_node_num_accelerators(root) == 2


def test_detect_node_resources_uses_the_manager(monkeypatch):
    monkeypatch.setattr(TPU, "get_current_node_num_accelerators",
                        staticmethod(lambda: 4))
    assert acc.detect_node_resources().get("TPU") == 4.0
    monkeypatch.setattr(TPU, "get_current_node_num_accelerators",
                        staticmethod(lambda: 0))
    assert "TPU" not in acc.detect_node_resources()


def test_unclaimed_worker_stays_on_cpu():
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    TPU.configure_worker_env(env, claimed=False)
    assert env == {"JAX_PLATFORMS": "cpu"}


def test_claimed_worker_sees_exactly_its_chips():
    # one chip of four
    env = {"JAX_PLATFORMS": "cpu"}
    TPU.configure_worker_env(env, claimed=True, device_ids=[2],
                             num_devices=4)
    assert env["JAX_PLATFORMS"] == "tpu,cpu"  # comes up on the chip or fails
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # an aligned pair: neighbours along x
    env = {}
    TPU.configure_worker_env(env, claimed=True, device_ids=[2, 3],
                             num_devices=4)
    assert env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"
    # the whole host needs no visibility variables
    env = {}
    TPU.configure_worker_env(env, claimed=True, device_ids=[0, 1, 2, 3],
                             num_devices=4)
    assert env == {"JAX_PLATFORMS": "tpu,cpu"}
    # not a topology libtpu can open
    for bad in ([1, 2], [0, 2], [0, 1, 2]):
        with pytest.raises(ValueError):
            acc.chip_visibility_env(bad, 4)


def test_claim_without_device_tree_leaves_jax_its_default():
    """TPU asserted as a resource on a host with no chips (every test
    cluster): nothing to hand over, and no platform is forced."""
    env = {"JAX_PLATFORMS": "cpu"}
    TPU.configure_worker_env(env, claimed=True, device_ids=(),
                             num_devices=0)
    assert env == {}


def test_aligned_block_allocation():
    assert _aligned_block([0, 1, 2, 3], 1) == [0]
    assert _aligned_block([1, 2, 3], 1) == [1]
    assert _aligned_block([1, 2, 3], 2) == [2, 3]
    assert _aligned_block([1, 3], 2) is None
    assert _aligned_block([0, 1, 2, 3], 4) == [0, 1, 2, 3]
    assert _aligned_block([0, 1, 3], 4) is None


def test_gpu_masking():
    env = {"CUDA_VISIBLE_DEVICES": "0,1"}
    acc.NvidiaGPUAcceleratorManager.configure_worker_env(env, claimed=False)
    assert env["CUDA_VISIBLE_DEVICES"] == ""
    acc.NvidiaGPUAcceleratorManager.configure_worker_env(env, claimed=True)
    assert "CUDA_VISIBLE_DEVICES" not in env


# ---------------------------------------------------------------------------
# The nodelet hands chips to the worker processes it spawns: k of n each,
# disjoint, returned when the process is gone.
# ---------------------------------------------------------------------------

@pytest.fixture
def four_chip_node(monkeypatch):
    """A nodelet that found four chips in its device tree. The workers
    never import jax — they report the environment they were given."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setattr(TPU, "get_current_node_num_accelerators",
                        staticmethod(lambda: 4))
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 8, "num_tpus": 4})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _chip_env():
    return {k: os.environ.get(k) for k in (
        "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")}


def test_workers_get_disjoint_chips_and_return_them(four_chip_node):
    import ray_tpu

    @ray_tpu.remote
    class Holder:
        def env(self):
            return _chip_env()

    @ray_tpu.remote(num_cpus=0)
    def cpu_task():
        return _chip_env()

    @ray_tpu.remote(num_tpus=1, num_cpus=0)
    def tpu_task():
        return _chip_env()

    one = Holder.options(num_tpus=1, num_cpus=0)
    a, b = one.remote(), one.remote()
    ea, eb = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=60)
    assert {ea["TPU_VISIBLE_CHIPS"], eb["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
    assert ea["JAX_PLATFORMS"] == eb["JAX_PLATFORMS"] == "tpu,cpu"
    assert ea["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    # a two-chip claim takes the aligned pair that is still whole
    pair = Holder.options(num_tpus=2, num_cpus=0).remote()
    ep = ray_tpu.get(pair.env.remote(), timeout=60)
    assert ep["TPU_VISIBLE_CHIPS"] == "2,3"
    assert ep["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"
    # a worker that claims no TPU is kept off the chips
    assert ray_tpu.get(cpu_task.remote(), timeout=60) == {
        "JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": None,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": None, "TPU_PROCESS_BOUNDS": None}
    # killed actors give their chips back; a TPU *task* worker then
    # takes one and keeps it open while idle...
    for h in (a, b, pair):
        ray_tpu.kill(h)
    et = ray_tpu.get(tpu_task.remote(), timeout=60)
    assert et["TPU_VISIBLE_CHIPS"] == "0"
    # ...until a whole-host worker needs it: the idle holder is told to
    # exit, and the new process owns all four (no visibility variables)
    whole = Holder.options(num_tpus=4, num_cpus=0).remote()
    ew = ray_tpu.get(whole.env.remote(), timeout=60)
    assert ew["JAX_PLATFORMS"] == "tpu,cpu"
    assert ew["TPU_VISIBLE_CHIPS"] is None
