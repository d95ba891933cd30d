"""Core API tests: init/remote/get/put/wait, errors, actors.

Mirrors the reference's basic test coverage (reference:
``python/ray/tests/test_basic.py``, ``test_actor.py``).
"""

import time

import pytest

import ray_tpu
from ray_tpu import exceptions


def test_put_get(ray_start_regular):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref) == 42
    refs = [ray_tpu.put(i) for i in range(10)]
    assert ray_tpu.get(refs) == list(range(10))


def test_put_objectref_rejected(ray_start_regular):
    ref = ray_tpu.put(1)
    with pytest.raises(TypeError):
        ray_tpu.put(ref)


def test_remote_function(ray_start_regular):
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1)) == 2
    assert ray_tpu.get([f.remote(i) for i in range(20)]) == list(range(1, 21))


def test_remote_with_options(ray_start_regular):
    @ray_tpu.remote(num_cpus=2)
    def f():
        return "ok"

    assert ray_tpu.get(f.remote()) == "ok"
    assert ray_tpu.get(f.options(num_cpus=1).remote()) == "ok"


def test_num_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def f():
        return 1, 2, 3

    a, b, c = f.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_dependency(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    x = add.remote(1, 2)
    y = add.remote(x, 3)
    z = add.remote(x, y)
    assert ray_tpu.get(z) == 9


def test_chain_many(ray_start_regular):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = ray_tpu.put(0)
    for _ in range(50):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref) == 50


def test_error_propagation(ray_start_regular):
    @ray_tpu.remote
    def fail():
        raise ValueError("boom")

    with pytest.raises(ValueError, match="boom"):
        ray_tpu.get(fail.remote())

    @ray_tpu.remote
    def dependent(x):
        return x

    # Error flows through dependencies without executing the dependent task.
    with pytest.raises(ValueError, match="boom"):
        ray_tpu.get(dependent.remote(fail.remote()))


def test_retry_exceptions(ray_start_regular):
    counter = {"n": 0}

    @ray_tpu.remote(max_retries=3, retry_exceptions=True)
    def flaky():
        counter["n"] += 1
        if counter["n"] < 3:
            raise RuntimeError("transient")
        return counter["n"]

    assert ray_tpu.get(flaky.remote()) == 3


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return "slow"

    a, b = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([a, b], num_returns=1, timeout=3)
    assert ready == [a]
    assert not_ready == [b]


def test_wait_timeout_none_ready(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(5)

    ready, not_ready = ray_tpu.wait([slow.remote()], timeout=0.05)
    assert ready == []
    assert len(not_ready) == 1


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(10)) == 21


def test_actor_basic(ray_start_regular):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, by=1):
            self.n += by
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote()) == 11
    assert ray_tpu.get(c.incr.remote(5)) == 16
    assert ray_tpu.get(c.value.remote()) == 16


def test_actor_ordering(ray_start_regular):
    @ray_tpu.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)

        def get(self):
            return self.items

    a = Appender.remote()
    for i in range(100):
        a.add.remote(i)
    assert ray_tpu.get(a.get.remote()) == list(range(100))


def test_actor_error(ray_start_regular):
    @ray_tpu.remote
    class A:
        def fail(self):
            raise KeyError("nope")

        def ok(self):
            return 1

    a = A.remote()
    with pytest.raises(KeyError):
        ray_tpu.get(a.fail.remote())
    # Actor survives method errors.
    assert ray_tpu.get(a.ok.remote()) == 1


def test_actor_init_failure(ray_start_regular):
    @ray_tpu.remote
    class Bad:
        def __init__(self):
            raise RuntimeError("init fail")

        def m(self):
            return 1

    b = Bad.remote()
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(b.m.remote(), timeout=10)


def test_kill_actor(ray_start_regular):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    ray_tpu.kill(a)
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(a.ping.remote(), timeout=10)


def test_named_actor(ray_start_regular):
    @ray_tpu.remote
    class Registry:
        def whoami(self):
            return "registry"

    Registry.options(name="reg").remote()
    h = ray_tpu.get_actor("reg")
    assert ray_tpu.get(h.whoami.remote()) == "registry"
    with pytest.raises(ValueError):
        ray_tpu.get_actor("missing")


def test_get_if_exists(ray_start_regular):
    @ray_tpu.remote
    class Singleton:
        def pid(self):
            return id(self)

    a = Singleton.options(name="s", get_if_exists=True).remote()
    b = Singleton.options(name="s", get_if_exists=True).remote()
    assert ray_tpu.get(a.pid.remote()) == ray_tpu.get(b.pid.remote())


def test_async_actor(ray_start_regular):
    import asyncio

    @ray_tpu.remote
    class AsyncActor:
        async def work(self, x):
            await asyncio.sleep(0.01)
            return x * 2

    a = AsyncActor.remote()
    refs = [a.work.remote(i) for i in range(10)]
    assert ray_tpu.get(refs) == [i * 2 for i in range(10)]


def test_actor_method_num_returns(ray_start_regular):
    @ray_tpu.remote
    class A:
        @ray_tpu.method(num_returns=2)
        def two(self):
            return 1, 2

    a = A.remote()
    x, y = a.two.remote()
    assert ray_tpu.get([x, y]) == [1, 2]


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(5)

    with pytest.raises(exceptions.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.05)


def test_runtime_context(ray_start_regular):
    ctx = ray_tpu.get_runtime_context()
    assert ctx.get_job_id()
    assert ctx.get_node_id()
    assert ctx.get_task_id() is None

    @ray_tpu.remote
    def f():
        return ray_tpu.get_runtime_context().get_task_id()

    assert ray_tpu.get(f.remote()) is not None


def test_cluster_resources(ray_start_regular):
    res = ray_tpu.cluster_resources()
    assert res["CPU"] == 4.0
    assert len(ray_tpu.nodes()) == 1


def test_object_ref_in_container(ray_start_regular):
    """Nested refs (inside a list) are NOT auto-resolved — parity with ray."""

    @ray_tpu.remote
    def f(refs):
        return ray_tpu.get(refs[0])

    inner = ray_tpu.put(7)
    assert ray_tpu.get(f.remote([inner])) == 7


def test_exit_actor(ray_start_regular):
    @ray_tpu.remote
    class A:
        def stop(self):
            ray_tpu.exit_actor()

        def ping(self):
            return "pong"

    a = A.remote()
    ray_tpu.get(a.stop.remote())
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(a.ping.remote(), timeout=10)


def test_resource_admission(ray_start_regular):
    """num_cpus admission limits true parallelism (4-CPU runtime)."""
    import threading

    running = []
    peak = [0]
    lock = threading.Lock()

    @ray_tpu.remote(num_cpus=2)
    def heavy(i):
        with lock:
            running.append(i)
            peak[0] = max(peak[0], len(running))
        time.sleep(0.15)
        with lock:
            running.remove(i)
        return i

    refs = [heavy.remote(i) for i in range(6)]
    assert sorted(ray_tpu.get(refs)) == list(range(6))
    assert peak[0] <= 2  # 4 CPUs / 2 per task


def test_blocked_get_releases_cpu(ray_start_regular):
    """Nested task trees must not deadlock: blocked parents release CPU."""

    @ray_tpu.remote(num_cpus=4)
    def parent():
        @ray_tpu.remote(num_cpus=4)
        def child():
            return "child-done"

        return ray_tpu.get(child.remote())

    assert ray_tpu.get(parent.remote(), timeout=10) == "child-done"


def test_available_resources_reflect_load(ray_start_regular):
    @ray_tpu.remote(num_cpus=3)
    def hold():
        time.sleep(0.5)

    ref = hold.remote()
    time.sleep(0.15)
    avail = ray_tpu.available_resources()
    assert avail["CPU"] == 1.0
    ray_tpu.get(ref)
    time.sleep(0.15)
    assert ray_tpu.available_resources()["CPU"] == 4.0


def test_inherited_async_actor(ray_start_regular):
    import asyncio

    class Base:
        async def work(self, x):
            await asyncio.sleep(0.01)
            return x + 1

    @ray_tpu.remote
    class Child(Base):
        pass

    c = Child.remote()
    assert ray_tpu.get(c.work.remote(1)) == 2


def test_named_actor_init_failure_unregisters(ray_start_regular):
    @ray_tpu.remote
    class Bad:
        def __init__(self):
            raise RuntimeError("nope")

        def m(self):
            return 1

    b = Bad.options(name="bad").remote()
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(b.m.remote(), timeout=10)
    # The name must be released so a replacement can be created.
    time.sleep(0.1)
    with pytest.raises(ValueError):
        ray_tpu.get_actor("bad")


def test_cancel_pending_task(ray_start_regular):
    @ray_tpu.remote(num_cpus=4)
    def blocker():
        time.sleep(1.0)

    @ray_tpu.remote(num_cpus=4)
    def victim():
        return "ran"

    b = blocker.remote()
    time.sleep(0.1)
    v = victim.remote()  # queued behind blocker
    ray_tpu.cancel(v)
    with pytest.raises(exceptions.TaskCancelledError):
        ray_tpu.get(v, timeout=10)
    ray_tpu.get(b)


def test_memory_store_wait_on_one_object_sleeps_on_its_event():
    """One object is waited for on its own event: ready at once when it
    is put (no polling step to wait out), and back at the timeout when
    it never comes."""
    import threading
    import time

    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.memory_store import MemoryStore

    store = MemoryStore()
    oid = ObjectID.from_random()
    threading.Timer(0.05, store.put, args=(oid, 7)).start()
    t0 = time.monotonic()
    ready, not_ready = store.wait([oid], 1, timeout=5.0)
    assert ready == [oid] and not_ready == [] and time.monotonic() - t0 < 1.0
    missing = ObjectID.from_random()
    t0 = time.monotonic()
    ready, not_ready = store.wait([missing], 1, timeout=0.1)
    assert ready == [] and not_ready == [missing]
    assert 0.09 <= time.monotonic() - t0 < 1.0
    assert store.wait([oid, missing], 1, timeout=0.1)[0] == [oid]
