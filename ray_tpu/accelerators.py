"""Accelerator plugin registry — the generic seam over device types.

Reference parity: ray._private.accelerators (accelerators/__init__.py
registry + AcceleratorManager ABC, accelerators/accelerator.py:23):
each accelerator type implements detection (how many on this node,
what type), node labeling, and per-worker visibility handoff; the
resource layer stays generic over the registry. TPU is the first-class
implementation (chips from the device tree, slice identity from
core/tpu.py, libtpu visibility variables per worker); the NVIDIA
manager shows the seam generalizes — it detects via the standard env/
driver paths and manages CUDA_VISIBLE_DEVICES, though no GPU exists in
this image to exercise it.
"""

from __future__ import annotations

import os
from typing import Sequence


class AcceleratorManager:
    """One accelerator family (reference: AcceleratorManager ABC —
    accelerator.py:23)."""

    # resource name in resource dicts ({"TPU": 1})
    resource_name: str = ""

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Devices physically present on this node (0 = none)."""
        raise NotImplementedError

    @staticmethod
    def get_current_node_accelerator_type() -> str | None:
        """Family/pod type string, e.g. "v5e" / "A100"."""
        return None

    @staticmethod
    def get_current_node_labels() -> dict[str, str]:
        """Identity labels to assert on the node (slice/topology)."""
        return {}

    @staticmethod
    def configure_worker_env(env: dict, claimed: bool,
                             device_ids: Sequence[int] = (),
                             num_devices: int = 0):
        """Mutate a worker's spawn env: hand `device_ids` (of the host's
        `num_devices`) through when the worker's resources claim the
        accelerator, hide it otherwise."""


class TPUAcceleratorManager(AcceleratorManager):
    """TPU chips of this host (reference: accelerators/tpu.py:19-170).

    Detection reads the device tree and never initialises a JAX
    backend: a chip belongs to one process at a time, and a head,
    nodelet or driver that opened it would take it from the worker it
    is meant for."""

    resource_name = "TPU"

    @staticmethod
    def get_current_node_num_accelerators(dev_root: str = "/dev") -> int:
        # one /dev/accelN per chip, or one numbered vfio group per chip
        # (/dev/vfio/vfio is the container node, not a chip)
        def count(path, is_chip):
            try:
                return sum(1 for f in os.listdir(path) if is_chip(f))
            except OSError:
                return 0

        return (count(dev_root, lambda f: f.startswith("accel")
                      and f[5:].isdigit())
                or count(os.path.join(dev_root, "vfio"), str.isdigit))

    @staticmethod
    def get_current_node_accelerator_type() -> str | None:
        from ray_tpu.core import tpu as tpu_mod

        return tpu_mod.detect_slice_labels().get(tpu_mod.POD_TYPE_LABEL)

    @staticmethod
    def get_current_node_labels() -> dict[str, str]:
        from ray_tpu.core import tpu as tpu_mod

        return tpu_mod.detect_slice_labels()

    @staticmethod
    def configure_worker_env(env: dict, claimed: bool,
                             device_ids: Sequence[int] = (),
                             num_devices: int = 0):
        if not claimed:
            env["JAX_PLATFORMS"] = "cpu"
            return
        if not device_ids:
            # TPU resource asserted on a host with no device tree
            # (tests): nothing to hand over, jax picks its default
            env.pop("JAX_PLATFORMS", None)
            return
        # the worker must come up on its chips or fail: with the
        # platform named, jax raises where it would otherwise drop to
        # the CPU backend
        env["JAX_PLATFORMS"] = "tpu,cpu"
        env.update(chip_visibility_env(device_ids, num_devices))


def chip_visibility_env(chip_ids: Sequence[int],
                        num_chips: int) -> dict[str, str]:
    """libtpu variables that make a process see exactly `chip_ids` of a
    host's `num_chips` (reference: TPU_VISIBLE_CHIPS management,
    accelerators/tpu.py:157-170). A process that owns the whole host
    needs none of them. Established on a v5e 2x2 host with libtpu
    0.0.34: one chip needs only TPU_VISIBLE_CHIPS (the bounds are
    accepted); two chips need the pair to be neighbours along x —
    ids (0,1) or (2,3) — declared as bounds 2,1,1 (1,2,1 fails at
    backend init)."""
    ids = list(chip_ids)
    k = len(ids)
    if k >= num_chips:
        return {}
    aligned = ids[0] % k == 0 and ids == list(range(ids[0], ids[0] + k))
    if k > 2 or not aligned:
        raise ValueError(
            f"chips {ids} of {num_chips} are not a topology libtpu can "
            f"open: a sub-host worker takes one chip, an aligned pair, "
            f"or the whole host")
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in ids),
        # a sub-host process is its own one-process topology
        "TPU_CHIPS_PER_PROCESS_BOUNDS": f"{k},1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


class NvidiaGPUAcceleratorManager(AcceleratorManager):
    """NVIDIA via the standard driver/env surface (reference:
    accelerators/nvidia_gpu.py). Present to prove the seam is generic;
    this image has no GPU."""

    resource_name = "GPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        try:
            return len(os.listdir("/proc/driver/nvidia/gpus"))
        except OSError:
            return 0

    @staticmethod
    def configure_worker_env(env: dict, claimed: bool,
                             device_ids: Sequence[int] = (),
                             num_devices: int = 0):
        if not claimed:
            env["CUDA_VISIBLE_DEVICES"] = ""
        else:
            env.pop("CUDA_VISIBLE_DEVICES", None)


_REGISTRY: dict[str, type[AcceleratorManager]] = {}


def register(manager: type[AcceleratorManager]):
    _REGISTRY[manager.resource_name] = manager
    return manager


def get_manager(resource_name: str) -> type[AcceleratorManager] | None:
    return _REGISTRY.get(resource_name)


def all_managers() -> dict[str, type[AcceleratorManager]]:
    return dict(_REGISTRY)


def detect_node_resources() -> dict[str, float]:
    """Auto-detected accelerator resources for this node (reference:
    resource autodetection at node start)."""
    out: dict[str, float] = {}
    for name, mgr in _REGISTRY.items():
        n = mgr.get_current_node_num_accelerators()
        if n > 0:
            out[name] = float(n)
    return out


register(TPUAcceleratorManager)
register(NvidiaGPUAcceleratorManager)
