"""Task/actor cancellation on the cluster runtime.

Reference: ``CoreWorker::CancelTask`` (``core_worker.h:961``) +
``CancelTaskOnExecutor`` (``core_worker.h:1655``): pending tasks are
dropped at their dispatch stage, running tasks are interrupted on the
executor (async-exc into the thread / asyncio task.cancel), ``force``
kills the worker, ``recursive`` walks the children.
"""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ray_tpu.is_initialized():    # left by an earlier file of this worker
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 2})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@ray_tpu.remote
def spin(seconds):
    # Python-level loop: an async-exc cancel fires between bytecodes.
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        time.sleep(0.01)
    return "done"


def test_cancel_pending_task():
    blockers = [spin.remote(5) for _ in range(2)]  # saturate 2 CPUs
    time.sleep(0.5)
    queued = spin.remote(5)  # sits in the sig queue
    ray_tpu.cancel(queued)
    with pytest.raises(exceptions.TaskCancelledError):
        ray_tpu.get(queued, timeout=30)
    for b in blockers:
        ray_tpu.cancel(b)


def test_cancel_running_task():
    ref = spin.remote(30)
    time.sleep(1.0)  # let it start
    ray_tpu.cancel(ref)
    t0 = time.monotonic()
    with pytest.raises(exceptions.TaskCancelledError):
        ray_tpu.get(ref, timeout=30)
    assert time.monotonic() - t0 < 10, "cancel did not interrupt the task"


def test_cancel_running_task_force():
    @ray_tpu.remote
    def c_blocked():
        time.sleep(30)  # C-level block: only force can stop it promptly
        return "done"

    ref = c_blocked.remote()
    time.sleep(1.0)
    ray_tpu.cancel(ref, force=True)
    t0 = time.monotonic()
    with pytest.raises(
            (exceptions.TaskCancelledError, exceptions.RayTaskError)):
        ray_tpu.get(ref, timeout=30)
    assert time.monotonic() - t0 < 15


def test_cancel_finished_task_is_noop():
    ref = spin.remote(0.01)
    assert ray_tpu.get(ref, timeout=30) == "done"
    ray_tpu.cancel(ref)  # must not raise or corrupt the result
    assert ray_tpu.get(ref, timeout=30) == "done"


def test_cancel_recursive():
    @ray_tpu.remote
    def parent():
        child = spin.remote(30)
        return ray_tpu.get(child)

    ref = parent.remote()
    time.sleep(1.5)  # parent started and submitted its child
    ray_tpu.cancel(ref, recursive=True)
    with pytest.raises(
            (exceptions.TaskCancelledError, exceptions.RayTaskError)):
        ray_tpu.get(ref, timeout=30)


def test_cancel_async_actor_task():
    @ray_tpu.remote
    class A:
        async def slow(self):
            await asyncio.sleep(30)
            return "done"

        async def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    ref = a.slow.remote()
    time.sleep(1.0)
    ray_tpu.cancel(ref)
    t0 = time.monotonic()
    with pytest.raises(exceptions.TaskCancelledError):
        ray_tpu.get(ref, timeout=30)
    assert time.monotonic() - t0 < 10
    # The actor survives a task cancel (only the coroutine died).
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"


def test_cancel_streaming_generator():
    @ray_tpu.remote
    def gen():
        for i in range(1000):
            time.sleep(0.05)
            yield i

    g = gen.options(num_returns="streaming").remote()
    it = iter(g)
    assert ray_tpu.get(next(it), timeout=30) == 0
    ray_tpu.cancel(g)
    with pytest.raises(
            (exceptions.TaskCancelledError, exceptions.RayTaskError,
             StopIteration)):
        for _ in range(1000):
            ray_tpu.get(next(it), timeout=30)


def test_cancel_queued_actor_task_no_sequence_hole():
    """Cancelling an actor task queued at the worker must not wedge the
    per-caller sequence: later calls still run."""

    @ray_tpu.remote
    class S:
        def slow(self, t):
            time.sleep(t)
            return "slow"

        def fast(self):
            return "fast"

    s = S.remote()
    r0 = s.slow.remote(2)
    r1 = s.slow.remote(5)  # waits for its turn behind r0
    time.sleep(0.5)
    ray_tpu.cancel(r1)
    with pytest.raises(exceptions.TaskCancelledError):
        ray_tpu.get(r1, timeout=30)
    t0 = time.monotonic()
    assert ray_tpu.get(s.fast.remote(), timeout=60) == "fast"
    assert time.monotonic() - t0 < 30, "sequence hole wedged the actor"
    assert ray_tpu.get(r0, timeout=30) == "slow"


def test_cancel_actor_task_beyond_send_window():
    """A task cancelled while gated (beyond the send window, never pushed)
    still advances the worker's sequence via the tombstone push."""

    @ray_tpu.remote
    class S:
        def slow(self, t):
            time.sleep(t)
            return "slow"

        def quick(self, i):
            return i

    s = S.remote()
    first = s.slow.remote(2)
    quicks = [s.quick.remote(i) for i in range(20)]  # 17+ gated
    ray_tpu.cancel(quicks[18])  # beyond the 16-wide window: not pushed yet
    results = []
    for i, q in enumerate(quicks):
        if i == 18:
            with pytest.raises(exceptions.TaskCancelledError):
                ray_tpu.get(q, timeout=60)
        else:
            results.append(ray_tpu.get(q, timeout=60))
    assert results == [i for i in range(20) if i != 18]
    assert ray_tpu.get(first, timeout=30) == "slow"
