"""Async (event-loop) actor execution on cluster workers.

Reference: async actors run their coroutine methods on a dedicated event
loop with fibers (``src/ray/core_worker/fiber.h``,
``transport/actor_scheduling_queue.h``); concurrency groups cap concurrent
execution per named group (``transport/concurrency_group_manager.h``).
Here the worker hosts one asyncio loop per async actor
(``workers/default_worker.py::_ActorRunner``); these tests run the same
semantics the local-runtime async tests cover, but on a real multi-process
cluster.
"""

import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module", autouse=True)
def cluster():
    # A runtime an earlier file of this worker left initialised (which
    # file comes first depends on the other workers' pace) would make
    # every test here an error at set-up.
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 8})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_async_actor_basic():
    @ray_tpu.remote
    class AsyncActor:
        async def work(self, x):
            await asyncio.sleep(0.01)
            return x * 2

    a = AsyncActor.remote()
    refs = [a.work.remote(i) for i in range(10)]
    assert ray_tpu.get(refs, timeout=60) == [i * 2 for i in range(10)]


def test_async_actor_overlaps_slow_calls():
    """8 concurrent 0.4s awaits must overlap (wall-clock ≪ 8×0.4s)."""

    @ray_tpu.remote
    class Sleeper:
        def __init__(self):
            self.cur = 0
            self.peak = 0

        async def nap(self):
            self.cur += 1
            self.peak = max(self.peak, self.cur)
            await asyncio.sleep(0.4)
            self.cur -= 1
            return self.peak

        async def peak_seen(self):
            return self.peak

    a = Sleeper.remote()
    t0 = time.monotonic()
    refs = [a.nap.remote() for _ in range(8)]
    ray_tpu.get(refs, timeout=60)
    elapsed = time.monotonic() - t0
    assert elapsed < 8 * 0.4 * 0.6, f"calls did not overlap: {elapsed:.2f}s"
    assert ray_tpu.get(a.peak_seen.remote(), timeout=30) >= 4


def test_async_actor_max_concurrency_cap():
    @ray_tpu.remote(max_concurrency=2)
    class Capped:
        def __init__(self):
            self.cur = 0
            self.peak = 0

        async def work(self):
            self.cur += 1
            self.peak = max(self.peak, self.cur)
            await asyncio.sleep(0.1)
            self.cur -= 1

        async def peak_seen(self):
            return self.peak

    a = Capped.remote()
    ray_tpu.get([a.work.remote() for _ in range(6)], timeout=60)
    peak = ray_tpu.get(a.peak_seen.remote(), timeout=30)
    assert peak == 2, f"expected concurrency capped at 2, saw {peak}"


def test_async_actor_concurrency_groups():
    """Methods in a cap-1 group serialize while default methods overlap."""

    @ray_tpu.remote(concurrency_groups={"solo": 1})
    class Grouped:
        def __init__(self):
            self.solo_cur = 0
            self.solo_peak = 0
            self.free_cur = 0
            self.free_peak = 0

        @ray_tpu.method(concurrency_group="solo")
        async def one_at_a_time(self):
            self.solo_cur += 1
            self.solo_peak = max(self.solo_peak, self.solo_cur)
            await asyncio.sleep(0.05)
            self.solo_cur -= 1

        async def free(self):
            self.free_cur += 1
            self.free_peak = max(self.free_peak, self.free_cur)
            await asyncio.sleep(0.05)
            self.free_cur -= 1

        async def peaks(self):
            return self.solo_peak, self.free_peak

    a = Grouped.remote()
    refs = [a.one_at_a_time.remote() for _ in range(4)]
    refs += [a.free.remote() for _ in range(4)]
    ray_tpu.get(refs, timeout=60)
    solo_peak, free_peak = ray_tpu.get(a.peaks.remote(), timeout=30)
    assert solo_peak == 1, f"solo group must serialize, saw {solo_peak}"
    assert free_peak >= 2, f"default group should overlap, saw {free_peak}"


def test_threaded_actor_concurrency_groups():
    """Concurrency groups on a sync class → threaded execution with caps."""

    @ray_tpu.remote(max_concurrency=4, concurrency_groups={"io": 2})
    class SyncGrouped:
        def __init__(self):
            self.lock = threading.Lock()
            self.cur = 0
            self.peak = 0

        @ray_tpu.method(concurrency_group="io")
        def io_call(self):
            with self.lock:
                self.cur += 1
                self.peak = max(self.peak, self.cur)
            time.sleep(0.1)
            with self.lock:
                self.cur -= 1

        def peak_seen(self):
            return self.peak

    a = SyncGrouped.remote()
    ray_tpu.get([a.io_call.remote() for _ in range(6)], timeout=60)
    peak = ray_tpu.get(a.peak_seen.remote(), timeout=30)
    assert peak <= 2, f"io group capped at 2, saw {peak}"


def test_async_actor_unknown_group_fails_typed():
    @ray_tpu.remote(concurrency_groups={"io": 2})
    class Bad:
        @ray_tpu.method(concurrency_group="nope")
        async def x(self):
            return 1

        async def ok(self):
            return 2

    a = Bad.remote()
    assert ray_tpu.get(a.ok.remote(), timeout=60) == 2
    with pytest.raises(ValueError, match="concurrency_group"):
        ray_tpu.get(a.x.remote(), timeout=60)


def test_async_actor_exception_propagates():
    @ray_tpu.remote
    class Boom:
        async def go(self):
            await asyncio.sleep(0.01)
            raise RuntimeError("async boom")

    a = Boom.remote()
    with pytest.raises(RuntimeError, match="async boom"):
        ray_tpu.get(a.go.remote(), timeout=60)


def test_async_generator_streaming():
    @ray_tpu.remote
    class Streamer:
        @ray_tpu.method(num_returns="streaming")
        async def gen(self, n):
            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * 10

    a = Streamer.remote()
    out = [ray_tpu.get(r, timeout=30) for r in a.gen.remote(5)]
    assert out == [0, 10, 20, 30, 40]


def test_async_actor_ordered_starts_per_caller():
    """Calls from one caller START in submission order (then interleave)."""

    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.starts = []

        async def mark(self, i):
            self.starts.append(i)
            await asyncio.sleep(0.01)
            return i

        async def get_starts(self):
            return self.starts

    a = Log.remote()
    ray_tpu.get([a.mark.remote(i) for i in range(10)], timeout=60)
    assert ray_tpu.get(a.get_starts.remote(), timeout=30) == list(range(10))


def test_async_normal_task():
    @ray_tpu.remote
    async def coro_task(x):
        await asyncio.sleep(0.01)
        return x + 1

    assert ray_tpu.get(coro_task.remote(41), timeout=60) == 42


def test_async_actor_exit_actor():
    @ray_tpu.remote
    class Quitter:
        async def ping(self):
            return "pong"

        async def quit(self):
            ray_tpu.exit_actor()

    a = Quitter.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    ray_tpu.get(a.quit.remote(), timeout=60)
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(a.ping.remote(), timeout=60)


def test_async_actor_concurrency_beyond_send_window():
    """One caller can overlap MORE than the ordered-actor send window
    (16): async actors widen the submitter window up to 48."""

    @ray_tpu.remote
    class Wide:
        def __init__(self):
            self.cur = 0
            self.peak = 0

        async def nap(self):
            self.cur += 1
            self.peak = max(self.peak, self.cur)
            await asyncio.sleep(0.6)
            self.cur -= 1

        async def peak_seen(self):
            return self.peak

    a = Wide.remote()
    ray_tpu.get([a.nap.remote() for _ in range(30)], timeout=120)
    peak = ray_tpu.get(a.peak_seen.remote(), timeout=30)
    assert peak > 16, f"async window still capped at 16 (peak={peak})"
