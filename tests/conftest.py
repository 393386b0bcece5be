"""Test fixtures.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4: the reference
tests multi-node logic on one box with faked resources; we test
multi-chip SPMD logic with faked devices). XLA_FLAGS must be set before
the backend initializes; jax_platforms is pinned through config so the
suite stays on the CPU even when run with JAX_PLATFORMS unset on a host
that has chips.
"""

import os
import sys

# Must happen before the first jax backend initialization.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


def _stop_leaked_runtime(module_name: str) -> None:
    """Shut down a runtime that is still up and fail, naming the module
    that left it. Left alone it fails every later test of this xdist
    worker at set-up (`ray_tpu.init() called twice`), under the names of
    modules that did nothing wrong."""
    ray_tpu = sys.modules.get("ray_tpu")
    if ray_tpu is None or not ray_tpu.is_initialized():
        return
    ray_tpu.shutdown()
    pytest.fail(f"{module_name} left a ray_tpu runtime running: a test "
                "or fixture of it called ray_tpu.init(), or used the "
                "API without it, and never ray_tpu.shutdown()")


@pytest.fixture(scope="module", autouse=True)
def no_runtime_left_running(request):
    """Module-scoped because fixtures all over tests/ hold a runtime for
    a whole module on purpose; autouse, so it is set up before and torn
    down after every other fixture of the module. Its value is the check
    itself, for the one test of it."""
    yield _stop_leaked_runtime
    _stop_leaked_runtime(request.module.__name__)


@pytest.fixture
def ray_local():
    """In-process local-mode runtime (reference fixture: ray_start_regular,
    python/ray/tests/conftest.py:532)."""
    import ray_tpu

    ray_tpu.init(local_mode=True, num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    """8-device mesh: data=2, fsdp=2, tensor=2."""
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=2, fsdp=2, tensor=2))


@pytest.fixture
def context_tile_pages(monkeypatch):
    """``set(pages)``: the serve programs read a lane's cached context in
    tiles of `pages` pages for the rest of the test. The toy models' rows
    fit a whole table into one tile of the real size
    (`KVLayout.tile_pages`), which would leave the tiled read's loop at
    one step."""
    def set_pages(pages: int) -> None:
        from ray_tpu.serve.llm.cache import KVLayout

        monkeypatch.setattr(KVLayout, "tile_pages",
                            property(lambda self: pages))
    return set_pages


@pytest.fixture
def read_by_kernel(monkeypatch):
    """``set(on)``: for the rest of the test (or until set again) the
    serve programs built from here on pick their context read as on a TPU
    (`on` true: a decode's and a verify's read of a full kind with K and V
    alike, or of a latent kind read whole, which has no V row, goes to the
    Pallas kernel, interpreted here; whatever the
    interpreter has no use for, whole tiles of the chip's memory, is not
    asked) or as the CPU does (false: the loops). It patches the one
    predicate that picks the path, `context_attention.reads_by_kernel`."""
    from ray_tpu.ops import context_attention as ca

    def on_tpu(layout, rows, sink=False):
        return (rows <= ca.KERNEL_ROWS and layout.window is None
                and layout.select is None and not sink
                and layout.v_row in (layout.row, 0))

    def set_path(on: bool) -> None:
        monkeypatch.setattr(ca, "reads_by_kernel",
                            on_tpu if on else (lambda *a, **k: False))
    return set_path
