"""Object store + serialization tests.

Reference model: plasma store tests exercise create/seal/get/evict on a
local segment without any cluster (src/ray/object_manager/plasma/).
"""

import multiprocessing as mp
import os

import numpy as np
import pytest

from ray_tpu.core import serialization as ser
from ray_tpu.core.object_store import (
    ObjectStoreFullError,
    SharedMemoryStore,
    native_lib,
    open_store,
)

needs_native = pytest.mark.skipif(native_lib() is None, reason="no g++ toolchain")


@pytest.fixture
def store():
    s = open_store(capacity=32 * 1024 * 1024)
    yield s
    s.close()
    s.unlink()


def test_put_get_roundtrip(store):
    oid = os.urandom(16)
    store.put(oid, b"payload")
    v = store.get(oid)
    assert bytes(v) == b"payload"
    del v
    store.release(oid)


def test_get_absent_returns_none(store):
    assert store.get(os.urandom(16)) is None
    assert not store.contains(os.urandom(16))


def test_create_seal_visibility(store):
    oid = os.urandom(16)
    buf = store.create(oid, 4)
    # unsealed objects are not gettable (plasma semantics)
    assert store.get(oid) is None
    buf[:] = b"abcd"
    del buf
    store.seal(oid)
    v = store.get(oid)
    assert bytes(v) == b"abcd"
    del v


def test_duplicate_create_raises(store):
    oid = os.urandom(16)
    store.put(oid, b"x")
    with pytest.raises(KeyError):
        store.create(oid, 1)


@needs_native
def test_eviction_under_pressure():
    s = SharedMemoryStore(capacity=8 * 1024 * 1024)
    try:
        ids = []
        for _ in range(40):
            oid = os.urandom(16)
            s.put(oid, bytes(1024 * 1024))
            ids.append(oid)
        st = s.stats()
        assert st["evictions"] > 0
        # newest objects survive (LRU evicts oldest)
        assert s.contains(ids[-1])
        assert not s.contains(ids[0])
    finally:
        s.close()
        s.unlink()


@needs_native
def test_referenced_objects_not_evicted():
    s = SharedMemoryStore(capacity=8 * 1024 * 1024)
    try:
        pinned = os.urandom(16)
        s.put(pinned, bytes(1024 * 1024))
        v = s.get(pinned)  # hold a ref
        for _ in range(40):
            s.put(os.urandom(16), bytes(1024 * 1024))
        assert s.contains(pinned)
        assert bytes(v[:1]) == b"\x00"
        del v
        s.release(pinned)
    finally:
        s.close()
        s.unlink()


@needs_native
def test_oversize_object_raises():
    s = SharedMemoryStore(capacity=4 * 1024 * 1024)
    try:
        with pytest.raises(ObjectStoreFullError):
            s.put(os.urandom(16), bytes(32 * 1024 * 1024))
    finally:
        s.close()
        s.unlink()


def _child_read(store_name: str, oid: bytes, q):
    from ray_tpu.core.object_store import open_store

    s = open_store(name=store_name, create=False)
    v = s.get(oid)
    q.put(bytes(v) if v is not None else None)
    del v
    s.release(oid)
    s.close()


@needs_native
def test_cross_process_get():
    s = SharedMemoryStore(capacity=8 * 1024 * 1024)
    try:
        oid = os.urandom(16)
        s.put(oid, b"cross-process")
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_read, args=(s.name, oid, q))
        p.start()
        assert q.get(timeout=30) == b"cross-process"
        p.join(timeout=10)
    finally:
        s.close()
        s.unlink()


# ---------------------------------------------------------------- serde


def test_serialize_numpy_zero_copy(store):
    arr = np.arange(1 << 18, dtype=np.float32)
    head, views, total = ser.serialize({"x": arr})
    oid = os.urandom(16)
    buf = store.create(oid, total)
    ser.write_into(buf, head, views)
    del buf
    store.seal(oid)
    out = ser.deserialize(store.get(oid))
    assert np.array_equal(out["x"], arr)


def test_dumps_loads_plain():
    for obj in [1, "s", [1, 2], {"k": (3, 4)}, None, b"bytes"]:
        assert ser.loads(ser.dumps(obj)) == obj


def test_serialize_jax_array():
    import jax.numpy as jnp

    x = jnp.arange(128, dtype=jnp.float32)
    out = ser.loads(ser.dumps({"x": x}))
    assert np.array_equal(np.asarray(out["x"]), np.asarray(x))


def test_native_build_is_atomic_under_concurrent_first_use(tmp_path):
    """Head, nodelet and workers of a fresh checkout all build the
    native libraries on first use, at once. Every one of them must load
    a complete library: one builds under the file lock, and the final
    name only ever holds a finished file."""
    import shutil
    import subprocess
    import sys

    import ray_tpu._native as native

    src_dir = os.path.dirname(native.__file__)
    pkg = tmp_path / "_native"
    pkg.mkdir()
    for f in ("__init__.py", "object_store.cc"):
        shutil.copy(os.path.join(src_dir, f), pkg / f)
    child = (
        "import ctypes, importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('nat', {str(pkg / '__init__.py')!r})\n"
        "nat = importlib.util.module_from_spec(spec); spec.loader.exec_module(nat)\n"
        "path = nat.build_library('object_store')\n"
        "assert path, 'no toolchain'\n"
        "ctypes.CDLL(path).rts_init\n"
        "print('LOADED')\n")
    procs = [subprocess.Popen([sys.executable, "-c", child],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True)
             for _ in range(12)]
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0 and "LOADED" in out, err[-2000:]
    left = sorted(os.listdir(pkg))
    assert "libobject_store.so" in left
    assert not [f for f in left if f.endswith(".tmp")], left
