"""Task/actor/object API semantics in local mode (reference test model:
python/ray/tests/test_basic.py family)."""

import time

import pytest

from ray_tpu.core.exceptions import ActorDiedError, GetTimeoutError, TaskError


def test_task_roundtrip(ray_local):
    ray = ray_local

    @ray.remote
    def add(a, b):
        return a + b

    assert ray.get(add.remote(1, 2)) == 3


def test_put_get(ray_local):
    ray = ray_local
    ref = ray.put({"x": [1, 2, 3]})
    assert ray.get(ref) == {"x": [1, 2, 3]}


def test_objectref_args_resolved(ray_local):
    ray = ray_local

    @ray.remote
    def double(x):
        return 2 * x

    ref = ray.put(21)
    assert ray.get(double.remote(ref)) == 42
    # chained tasks
    assert ray.get(double.remote(double.remote(ref))) == 84


def test_num_returns(ray_local):
    ray = ray_local

    @ray.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates(ray_local):
    ray = ray_local

    @ray.remote
    def boom():
        raise ValueError("nope")

    with pytest.raises(TaskError, match="nope"):
        ray.get(boom.remote())


def test_retry_exceptions(ray_local):
    ray = ray_local
    state = {"n": 0}

    @ray.remote(max_retries=3, retry_exceptions=True)
    def flaky():
        state["n"] += 1
        if state["n"] < 3:
            raise RuntimeError("transient")
        return state["n"]

    assert ray.get(flaky.remote()) == 3


def test_wait(ray_local):
    ray = ray_local

    @ray.remote
    def fast():
        return "fast"

    @ray.remote
    def slow():
        time.sleep(5)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray.wait([f, s], num_returns=1, timeout=2)
    assert ready == [f] and not_ready == [s]


def test_get_timeout(ray_local):
    ray = ray_local

    @ray.remote
    def slow():
        time.sleep(10)

    with pytest.raises(GetTimeoutError):
        ray.get(slow.remote(), timeout=0.1)


def test_actor_state_and_order(ray_local):
    ray = ray_local

    @ray.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    refs = [c.inc.remote() for _ in range(5)]
    assert ray.get(refs) == [11, 12, 13, 14, 15]
    assert ray.get(c.value.remote()) == 15


def test_named_actor(ray_local):
    ray = ray_local

    @ray.remote
    class Store:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v

        def get(self, k):
            return self.d.get(k)

    Store.options(name="kv").remote()
    h = ray.get_actor("kv")
    ray.get(h.set.remote("a", 1))
    assert ray.get(h.get.remote("a")) == 1

    with pytest.raises(ValueError):
        Store.options(name="kv").remote()
    # get_if_exists returns the existing one
    h2 = Store.options(name="kv", get_if_exists=True).remote()
    assert ray.get(h2.get.remote("a")) == 1


def test_kill_actor(ray_local):
    ray = ray_local

    @ray.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray.get(a.ping.remote()) == "pong"
    ray.kill(a)
    with pytest.raises(ActorDiedError):
        ray.get(a.ping.remote())


def test_actor_error_propagates(ray_local):
    ray = ray_local

    @ray.remote
    class B:
        def bad(self):
            raise KeyError("missing")

    b = B.remote()
    with pytest.raises(TaskError, match="missing"):
        ray.get(b.bad.remote())


def test_nested_tasks(ray_local):
    ray = ray_local

    @ray.remote
    def inner(x):
        return x * 2

    @ray.remote
    def outer(x):
        import ray_tpu

        return ray_tpu.get(inner.remote(x)) + 1

    assert ray.get(outer.remote(10)) == 21


def test_actor_handle_passing(ray_local):
    ray = ray_local

    @ray.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    @ray.remote
    def bump(counter):
        import ray_tpu

        return ray_tpu.get(counter.inc.remote())

    c = Counter.remote()
    assert ray.get(bump.remote(c)) == 1
    assert ray.get(bump.remote(c)) == 2


def test_runtime_context(ray_local):
    ray = ray_local
    ctx = ray.get_runtime_context()
    assert len(ctx.get_node_id()) == 32


def test_options_validation(ray_local):
    ray = ray_local
    with pytest.raises(ValueError, match="invalid task option"):

        @ray.remote(bogus_option=1)
        def f():
            pass


def test_a_module_that_leaves_a_runtime_up_is_named(no_runtime_left_running):
    """conftest's module fixture: a runtime still up when a module's
    last fixture has gone is shut down and charged to that module, by
    name, so the next module's tests start clean."""
    import ray_tpu

    check = no_runtime_left_running
    check("tests.some_module")  # nothing is up: nothing is said
    ray_tpu.init(local_mode=True, num_cpus=1)
    with pytest.raises(pytest.fail.Exception,
                       match="tests.some_module left a ray_tpu runtime"):
        check("tests.some_module")
    assert not ray_tpu.is_initialized()
    ray_tpu.init(local_mode=True, num_cpus=1)  # and init() works again
    ray_tpu.shutdown()
