"""ObjectRef: a future-like handle to a (possibly remote, possibly pending) object.

Re-design of the reference ObjectRef (reference: ``python/ray/_raylet.pyx``
``ObjectRef``): carries the 28-byte ``ObjectID`` (task lineage + index) and the
owner's address. Refcounting hooks (``_register``/``_release``) notify the
runtime on creation/GC so distributed reference counting can free the value.
"""

from __future__ import annotations

import time
from typing import Optional

from ray_tpu._private.ids import ObjectID, TaskID

# Index space for streamed generator items: distinct from declared returns
# (0..n-1) and put-scoped ids (2^31 + k).
STREAM_INDEX_BASE = 1 << 30


def drain_stream(gen, task_id: TaskID, put) -> int:
    """Drain a streaming-generator task: each yielded value becomes its own
    store object at the deterministic stream id the consumer's
    ObjectRefGenerator polls; the returned count rides the task's declared
    return (reference: ObjectRefStream, ``task_manager.h:104``). ``put`` is
    the executor's object sink ``(ObjectID, value) -> None``. The single
    implementation keeps the id scheme/count protocol identical across the
    local, async-actor, and cluster-worker executors."""
    i = 0
    for item in gen:
        put(ObjectID.from_task(task_id, STREAM_INDEX_BASE + i), item)
        i += 1
    return i


async def drain_stream_async(agen, task_id: TaskID, put) -> int:
    """Async-generator variant of :func:`drain_stream`."""
    i = 0
    async for item in agen:
        put(ObjectID.from_task(task_id, STREAM_INDEX_BASE + i), item)
        i += 1
    return i


class ObjectRef:
    __slots__ = ("_id", "_owner_address", "_call_site", "_registered", "__weakref__")

    def __init__(self, object_id: ObjectID, owner_address: str = "", call_site: str = "",
                 skip_ref_count: bool = False):
        self._id = object_id
        self._owner_address = owner_address
        self._call_site = call_site
        self._registered = False
        if not skip_ref_count:
            from ray_tpu._private import worker as _worker

            w = _worker.global_worker_or_none()
            if w is not None:
                w.core.add_local_reference(self)
                self._registered = True

    # -- identity ---------------------------------------------------------
    def id(self) -> ObjectID:
        return self._id

    def binary(self) -> bytes:
        return self._id.binary()

    def hex(self) -> str:
        return self._id.hex()

    def task_id(self) -> TaskID:
        return self._id.task_id()

    def owner_address(self) -> str:
        return self._owner_address

    def call_site(self) -> str:
        return self._call_site

    @classmethod
    def from_binary(cls, binary: bytes, owner_address: str = "") -> "ObjectRef":
        return cls(ObjectID(binary), owner_address)

    @classmethod
    def nil(cls) -> "ObjectRef":
        return cls(ObjectID.nil(), skip_ref_count=True)

    # -- semantics --------------------------------------------------------
    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def __reduce__(self):
        # Plain pickling (outside the framework serializer) keeps id + owner.
        # Serialization IS escape: if this process holds the object's bytes
        # lazily (inline task result not yet flushed to the node store),
        # flush now — whoever receives this ref resolves it through the
        # directory. Covers every pickle path in one place: task results,
        # stream items, gateway replies, user pickles.
        try:
            from ray_tpu._private import worker as _worker

            w = _worker.global_worker_or_none()
            if w is not None:
                hook = getattr(w.core, "_flush_escaped", None)
                if hook is not None:
                    hook((self._id.binary(),))
        except Exception:  # noqa: BLE001 — escape flush is best-effort
            pass
        return (_rebuild_ref, (self._id.binary(), self._owner_address))

    def __del__(self):
        if getattr(self, "_registered", False):
            try:
                from ray_tpu._private import worker as _worker

                w = _worker.global_worker_or_none()
                if w is not None:
                    w.core.remove_local_reference(self._id)
            except Exception:
                pass

    def future(self):
        """Return a concurrent.futures.Future resolving to the object's value."""
        from ray_tpu._private import worker as _worker

        return _worker.global_worker().core.as_future(self)

    def __await__(self):
        import asyncio

        return asyncio.wrap_future(self.future()).__await__()


def _rebuild_ref(binary: bytes, owner_address: str) -> ObjectRef:
    ref = ObjectRef(ObjectID(binary), owner_address)
    return ref


class ObjectRefGenerator:
    """Stream of ObjectRefs from a generator task (reference:
    ``ObjectRefStream``, ``task_manager.h:104`` / ``_raylet.pyx:284``).

    Yields the ref of item *i* as soon as the executor has stored it — the
    task may still be running. Iteration ends when the task finishes and
    ``i`` reaches the item count (carried by the task's declared return).
    ``num_returns="streaming"`` (or ``"dynamic"``) on a generator task
    returns one of these from ``.remote()``.
    """

    def __init__(self, length_ref: ObjectRef, owner_address: str = ""):
        self._length_ref = length_ref
        self._task_id = length_ref.task_id()
        self._owner_address = owner_address
        self._i = 0
        self._length: Optional[int] = None
        self._exhausted = False

    def _check_length(self) -> Optional[int]:
        if self._length is not None:
            return self._length
        from ray_tpu._private import worker as _worker

        core = _worker.global_worker().core
        ready, _ = core.wait([self._length_ref], num_returns=1, timeout=0,
                             fetch_local=True)
        if ready:
            n = core.get([self._length_ref], timeout=30)[0]
            self._length = int(n)
        return self._length

    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        # Blocks until the item arrives, the stream ends, or the task's
        # stored error surfaces via the length ref — task failure (incl.
        # worker death) always stores an error there, so no deadline is
        # needed for liveness (reference: generator __next__ blocks).
        return self._next_internal(timeout=None)

    def _next_internal(self, timeout: Optional[float]) -> ObjectRef:
        from ray_tpu import exceptions
        from ray_tpu._private import worker as _worker

        core = _worker.global_worker().core
        oid = ObjectID.from_task(self._task_id, STREAM_INDEX_BASE + self._i)
        ref = ObjectRef(oid, owner_address=self._owner_address)
        deadline = None if timeout is None else time.monotonic() + timeout
        stall_deadline = None
        # The item's arrival ends the wait at once (its own event); a
        # wait that times out only looks whether the stream has ENDED.
        # From 50 ms, doubling to 0.4 s: an end straight after an item is
        # seen as soon as ever, and a consumer whose item is far off (a
        # request queued behind a full engine, a stream whose replica
        # ships a batch a second) stops waking twenty times a second;
        # 512 of those were 10,000 wake-ups a second on the interpreter
        # lock the producers need (PR 38).
        poll = 0.05
        while True:
            # Item readiness first: items yielded before a mid-stream
            # failure must stay consumable (the length check below raises
            # the task's stored error once we're past the stored items).
            ready, _ = core.wait([ref], num_returns=1, timeout=poll,
                                 fetch_local=True)
            if ready:
                self._i += 1
                return ref
            poll = min(2 * poll, 0.4)
            n = self._check_length()
            if n is not None and self._i >= n:
                self._exhausted = True
                raise StopIteration
            if n is not None:
                # The count says this item was produced, so a long miss
                # means its copies were lost (e.g. the producing node
                # died). Stream ids carry no lineage of their own; the
                # *length ref* does, and re-executing its task regenerates
                # every item at the same deterministic ids.
                if stall_deadline is None:
                    stall_deadline = time.monotonic() + 10.0
                elif time.monotonic() > stall_deadline:
                    stall_deadline = None
                    rec = getattr(core, "_maybe_reconstruct", None)
                    if rec is None or not rec(self._length_ref):
                        raise exceptions.ObjectLostError(
                            f"streamed item {self._i} of task "
                            f"{self._task_id.hex()[:16]} was lost and "
                            f"cannot be reconstructed")
            if deadline is not None and time.monotonic() > deadline:
                raise exceptions.GetTimeoutError(
                    f"streamed item {self._i} of task "
                    f"{self._task_id.hex()[:16]} did not arrive in "
                    f"{timeout}s")

    def ready(self) -> bool:
        """Whether the next item is already stored, so that ``next()``
        returns its ref without waiting. Never blocks."""
        from ray_tpu._private import worker as _worker

        ref = ObjectRef(
            ObjectID.from_task(self._task_id, STREAM_INDEX_BASE + self._i),
            owner_address=self._owner_address, skip_ref_count=True)
        ready, _ = _worker.global_worker().core.wait(
            [ref], num_returns=1, timeout=0, fetch_local=True)
        return bool(ready)

    def completed(self) -> ObjectRef:
        """Ref resolving when the whole stream has been produced."""
        return self._length_ref

    def __del__(self):
        # Abandoned mid-stream: the tail items have no registered holder,
        # so ask the runtime to reap them once the stream length resolves
        # (reference: ObjectRefStream deletion on generator GC).
        if getattr(self, "_exhausted", True):
            return
        try:
            from ray_tpu._private import worker as _worker

            w = _worker.global_worker_or_none()
            if w is not None:
                reap = getattr(w.core, "release_stream_tail", None)
                if reap is not None:
                    reap(self._length_ref, self._i)
        except Exception:  # noqa: BLE001
            pass

    def __repr__(self):
        return (f"ObjectRefGenerator(task={self._task_id.hex()[:16]}, "
                f"next={self._i})")
