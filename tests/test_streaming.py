"""Streaming generators + promoted task payloads (reference:
python/ray/tests/test_streaming_generator.py + plasma-promoted args,
core_worker.cc:1527)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module")
def stream_cluster():
    c = Cluster(head_node_args={"num_cpus": 4})
    c.add_node(num_cpus=4)
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


# ------------------------------------------------------------- local mode

def test_local_streaming_generator(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    got = [ray_tpu.get(ref) for ref in gen.remote(5)]
    assert got == [0, 10, 20, 30, 40]


def test_local_dynamic_alias(ray_start_regular):
    @ray_tpu.remote(num_returns="dynamic")
    def gen():
        yield "a"
        yield "b"

    refs = list(gen.remote())
    assert [ray_tpu.get(r) for r in refs] == ["a", "b"]


def test_local_streaming_error_surfaces(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1
        raise ValueError("stream broke")

    it = gen.remote()
    assert ray_tpu.get(next(it)) == 1
    with pytest.raises(ValueError, match="stream broke"):
        for ref in it:
            ray_tpu.get(ref)


def test_local_streaming_non_generator_errors(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def notgen():
        return 5

    it = notgen.remote()
    with pytest.raises(TypeError, match="requires a generator"):
        for r in it:
            ray_tpu.get(r)


def test_local_actor_class_level_streaming(ray_start_regular):
    """num_returns='streaming' at the class level must stream too (the
    streaming decision and submit path share the merged options)."""

    @ray_tpu.remote(num_returns="streaming")
    class G:
        def stream(self, n):
            for i in range(n):
                yield i * 5

    a = G.remote()
    it = a.stream.remote(3)
    assert isinstance(it, ray_tpu.ObjectRefGenerator)
    assert [ray_tpu.get(r, timeout=30) for r in it] == [0, 5, 10]


def test_local_async_actor_streaming(ray_start_regular):
    @ray_tpu.remote
    class AGen:
        async def ping(self):  # marks the actor async
            return "pong"

        async def astream(self, n):
            for i in range(n):
                yield i * 2

        def sstream(self, n):
            for i in range(n):
                yield i + 7

    a = AGen.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    it = a.astream.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r, timeout=30) for r in it] == [0, 2, 4]
    # Sync generator methods stream on async actors too.
    it = a.sstream.options(num_returns="streaming").remote(2)
    assert [ray_tpu.get(r, timeout=30) for r in it] == [7, 8]


def test_local_stream_says_when_its_next_item_is_stored(ray_start_regular):
    """``ready()`` is true exactly while ``next()`` would not wait, and
    takes nothing from the stream."""
    import threading

    go = threading.Event()

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1
        yield 2
        go.wait(30)
        yield 3

    it = gen.remote()
    assert ray_tpu.get(next(it), timeout=30) == 1
    deadline = time.monotonic() + 30
    while not it.ready():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert it.ready()                   # asking consumes nothing
    assert ray_tpu.get(next(it), timeout=30) == 2
    assert not it.ready()               # the producer is held at item 3
    go.set()
    assert ray_tpu.get(next(it), timeout=30) == 3
    assert not it.ready()               # the end is not an item
    with pytest.raises(StopIteration):
        next(it)


def test_local_abandoned_stream_tail_reaped(ray_start_regular):
    """Dropping an ObjectRefGenerator mid-stream must not pin the tail
    items in the store forever."""
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_ref import STREAM_INDEX_BASE

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(10):
            yield i

    it = gen.remote()
    task_id = it.completed().task_id()
    assert ray_tpu.get(next(it), timeout=30) == 0
    ray_tpu.get(it.completed(), timeout=30)  # all 10 items stored
    core = worker_mod.global_worker().core
    tail_id = ObjectID.from_task(task_id, STREAM_INDEX_BASE + 5)
    assert core.store.contains(tail_id)
    del it
    import gc

    gc.collect()
    deadline = time.monotonic() + 10
    while core.store.contains(tail_id) and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not core.store.contains(tail_id)


def test_local_actor_init_failure_fails_queued_calls(ray_start_regular):
    """Calls queued while __init__ is failing get ActorDiedError (not a
    hang) — exercises the inbox drain in _LocalActor._die."""

    @ray_tpu.remote
    class FailsInit:
        def __init__(self):
            time.sleep(0.5)
            raise RuntimeError("boom")

        def m(self):
            return 1

    a = FailsInit.remote()
    refs = [a.m.remote() for _ in range(3)]
    for r in refs:
        with pytest.raises(ray_tpu.exceptions.ActorDiedError):
            ray_tpu.get(r, timeout=30)


# ----------------------------------------------------------- cluster mode

def test_cluster_streaming_before_completion(stream_cluster):
    """Items are consumable while the task is still running — the point of
    ObjectRefStream vs materialize-then-return."""

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(4):
            yield i
            time.sleep(0.5)

    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get(warm.remote(), timeout=60)  # exclude worker spawn latency
    start = time.monotonic()
    it = slow_gen.remote()
    first = ray_tpu.get(next(it), timeout=30)
    first_latency = time.monotonic() - start
    assert first == 0
    # Task takes ~2s total; the first item must arrive well before that.
    assert first_latency < 1.5, first_latency
    rest = [ray_tpu.get(r, timeout=30) for r in it]
    assert rest == [1, 2, 3]


def test_cluster_stream_says_when_its_next_item_is_stored(stream_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1
        yield 2
        time.sleep(1.0)
        yield 3

    it = gen.remote()
    assert ray_tpu.get(next(it), timeout=60) == 1
    deadline = time.monotonic() + 30
    while not it.ready():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert ray_tpu.get(next(it), timeout=30) == 2
    assert not it.ready()
    assert [ray_tpu.get(r, timeout=30) for r in it] == [3]
    assert not it.ready()


def test_cluster_streaming_large_items(stream_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(3):
            yield np.full(200_000, i, dtype=np.float32)  # 800KB each

    vals = [ray_tpu.get(r, timeout=60) for r in gen.remote()]
    assert [int(v[0]) for v in vals] == [0, 1, 2]
    assert all(v.shape == (200_000,) for v in vals)


def test_cluster_actor_streaming(stream_cluster):
    @ray_tpu.remote
    class Gen:
        def stream(self, n):
            for i in range(n):
                yield i + 100

    a = Gen.remote()
    it = a.stream.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r, timeout=30) for r in it] == [100, 101, 102]


def test_cluster_streaming_non_generator_errors(stream_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def notgen():
        return "abc"  # iterable but NOT a generator: must not mis-stream

    it = notgen.remote()
    with pytest.raises(TypeError, match="requires a generator"):
        for r in it:
            ray_tpu.get(r, timeout=30)


def test_cluster_large_arg_promotion(stream_cluster):
    """>100KB payloads travel by object ref, not inline in the TaskSpec."""
    big = np.arange(500_000, dtype=np.float64)  # 4MB

    @ray_tpu.remote
    def total(arr, scale):
        return float(arr.sum()) * scale

    assert ray_tpu.get(total.remote(big, 2.0), timeout=60) == \
        float(big.sum()) * 2.0


def test_cluster_large_arg_survives_worker_crash_retry(stream_cluster, tmp_path):
    """The promoted payload stays in the store, so a crash-retry re-ships an
    object id instead of failing (and reconstruction has the bytes)."""
    marker = tmp_path / "crashed_once"
    big = np.ones(300_000, dtype=np.float64)  # 2.4MB

    @ray_tpu.remote(max_retries=2)
    def flaky_sum(arr, marker_path):
        import os

        if not os.path.exists(marker_path):
            open(marker_path, "w").close()
            os._exit(1)  # simulated worker crash on first attempt
        return float(arr.sum())

    assert ray_tpu.get(flaky_sum.remote(big, str(marker)), timeout=120) == \
        float(big.sum())
    assert marker.exists()
