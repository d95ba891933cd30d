"""ray_tpu.serve: model serving (reference: ``python/ray/serve``).

Condensed re-design of SURVEY.md §3.5's architecture:

* ``ServeController`` (named actor, ``serve/_private/controller.py:84``):
  holds deployment specs, reconciles replica actors (create/kill/restart on
  death), serves the routing table to handles.
* ``Replica`` actors (``replica.py:879``): host the user callable with high
  max_concurrency (async-replica analog); ``@serve.batch`` methods batch
  concurrent calls.
* ``DeploymentHandle`` (``handle.py:625``): routes each call with
  power-of-two-choices on per-replica in-flight counts
  (``replica_scheduler/pow_2_scheduler.py:813``'s local approximation).
* Autoscaling (``_private/autoscaling_policy.py``): replicas count ongoing
  requests; the controller scales toward ``total_ongoing / target`` within
  ``[min_replicas, max_replicas]``, applying upscale/downscale delays.
* Push-based routing (``_private/long_poll.py:204``): the controller
  publishes a route-change event over the GCS pubsub whenever a
  deployment's replica set changes; handles refresh on the event instead of
  polling on a TTL, and a call that lands on a dead replica refreshes and
  retries immediately.
* Data plane: an asyncio HTTP/1.1 ingress (keep-alive, chunked streaming,
  bounded-executor admission) plus a gRPC ingress over one shared router,
  and declarative YAML/REST deploys — see :mod:`ray_tpu.serve.proxy` and
  :mod:`ray_tpu.serve.config` (reference ``proxy.py:532,752``).
"""

from __future__ import annotations

import collections
import json
import logging
import math
import os
import random
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu._private import events as _events

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "__serve_controller__"
ROUTES_CHANNEL = "SERVE_ROUTES"

# In-process route-event bus for the single-process (local) runtime, where
# controller and handles share the interpreter; cluster mode rides the GCS
# pubsub instead.
_LOCAL_BUS: List[Callable[[str], None]] = []


def _core():
    from ray_tpu._private import worker as worker_mod

    return worker_mod.global_worker().core


def _publish_route_event(name: str) -> None:
    core = _core()
    if hasattr(core, "gcs"):
        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        try:
            core.gcs.Publish(pb.PublishRequest(
                channel=ROUTES_CHANNEL, data=name.encode()))
            return
        except Exception:  # noqa: BLE001
            pass
    for cb in list(_LOCAL_BUS):
        try:
            cb(name)
        except Exception:  # noqa: BLE001
            pass


def _subscribe_route_events(cb: Callable[[str], None]) -> None:
    core = _core()
    if hasattr(core, "gcs"):
        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        def loop():
            sub_id = f"serve-{uuid.uuid4().hex[:12]}"
            while True:
                try:
                    stream = core.gcs.Subscribe(pb.SubscribeRequest(
                        channels=[ROUTES_CHANNEL], subscriber_id=sub_id))
                    for msg in stream:
                        cb(msg.data.decode())
                except Exception:  # noqa: BLE001
                    time.sleep(0.5)

        threading.Thread(target=loop, daemon=True,
                         name="serve-routes-sub").start()
    else:
        _LOCAL_BUS.append(cb)


DEFAULT_AUTOSCALING = {
    "min_replicas": 1,
    "max_replicas": 4,
    "target_ongoing_requests": 2.0,
    "upscale_delay_s": 0.3,
    "downscale_delay_s": 2.0,
    # Engine-pressure policy (fed by the per-replica pressure fan-out):
    # desired replicas also scale on admission-queue depth per replica,
    # on paged-KV arena starvation (every engine replica with zero
    # free+reclaimable blocks), and on ingress sheds observed since the
    # last decision — replicas scale on ENGINE pressure, not just the
    # router's ongoing-count. 0 disables a signal.
    "target_queue_depth": 4.0,
    "kv_starvation_upscale": True,
    "shed_upscale": True,
    # Disaggregated-role signals (0/off by default — generic
    # deployments never pay them): prefill fleets scale on waiting
    # prompt tokens per replica; decode fleets add a replica when EVERY
    # engine's importable-block headroom (free + LRU-reclaimable)
    # drops under the floor — the next KV handoff's reservation is
    # about to fail.
    "target_prefill_queue_tokens": 0.0,
    "importable_floor": 0.0,
}


# ------------------------------------------------------------ role groups
# Disaggregated prefill/decode topology: a LOGICAL deployment name maps
# to its (prefill, decode) deployment pair. The ingress consults this to
# classify-and-split requests; everything else (autoscaler, pool
# arbiter, pressure fan-out) sees two ordinary deployments that scale
# independently. Registered in the ingress/router process (the only
# consumer) — `serve.run` the two deployments first, then declare the
# group; the YAML deploy path does both from a `role_groups:` section.
_ROLE_GROUPS: Dict[str, Dict[str, str]] = {}
_ROLE_GROUPS_LOCK = threading.Lock()


def register_role_group(name: str, *, prefill: str, decode: str) -> None:
    """Declare ``name`` as a disaggregated role group: streaming LLM
    requests to ``name`` are classified at the ingress and either split
    (prefill on ``prefill``, KV handoff, decode on ``decode``) or sent
    to ``decode`` whole (its engines run colocated admission too)."""
    if not prefill or not decode:
        raise ValueError("role group needs both a prefill and a decode "
                         "deployment name")
    with _ROLE_GROUPS_LOCK:
        _ROLE_GROUPS[name] = {"prefill": prefill, "decode": decode}


def get_role_group(name: str) -> Optional[Dict[str, str]]:
    with _ROLE_GROUPS_LOCK:
        g = _ROLE_GROUPS.get(name)
        return dict(g) if g else None


def unregister_role_group(name: str) -> bool:
    with _ROLE_GROUPS_LOCK:
        return _ROLE_GROUPS.pop(name, None) is not None


# The replica actor's concurrency group for what the CONTROLLER asks of
# it (health, metrics, pressure, node id, drain). Requests run in the
# default group, capped at the deployment's ``max_ongoing_requests``; a
# caller pool larger than that cap keeps every one of those places taken
# for as long as the pool lasts, with more requests queued behind them.
# A probe in the same queue would go unanswered for as long, and the
# controller would kill a healthy, full replica after
# ``REPLICA_STARTUP_GRACE_S``; in a group of its own it is answered
# whatever the requests do.
CONTROL_GROUP = "control"
CONTROL_CONCURRENCY = 8


class StreamBatch(list):
    """What a stream held when the replica's loop next reached it, as
    ONE streamed object: a stream that keeps up ships its items one at a
    time, as ever; one that has fallen behind (hundreds of open streams
    share the replica's loop, the object store and the interpreter
    lock) ships what has piled up, so the cost of a turn is paid once
    for all of it and the stream catches up. Made by
    :meth:`Replica._stream_sync_generator` for a sync generator's items
    and by the engine deployments' token streams
    (``ray_tpu.llm._TokenStream``), which the loop drives itself.
    :class:`DeploymentResponseGenerator` hands the items out one by
    one, so a consumer never sees a batch."""


_STREAM_OVER = object()


class _StreamRaised:
    def __init__(self, error: BaseException):
        self.error = error


class Replica:
    """Hosts one copy of the user callable.

    Async-native (reference: Serve replicas run user code on the replica
    actor's event loop, ``serve/_private/replica.py``): ``handle_request``
    is a coroutine, so the replica actor runs on a dedicated asyncio loop
    and an async user ``__call__`` overlaps slow requests up to the
    deployment's ``max_concurrency``. Sync user code runs in a thread
    executor so it still overlaps (threaded-deployment behavior) instead
    of blocking the loop.
    """

    def __init__(self, cls_or_fn, init_args, init_kwargs, is_function: bool,
                 sync_workers: int = 8):
        import inspect
        from concurrent.futures import ThreadPoolExecutor as _TPE

        self.is_function = is_function
        if is_function:
            self.instance = cls_or_fn
        else:
            self.instance = cls_or_fn(*init_args, **(init_kwargs or {}))
        self._ongoing = 0
        self._total = 0
        self._draining = False
        self._m_lock = threading.Lock()
        self._inspect = inspect
        self._sync_pool = _TPE(max_workers=max(1, int(sync_workers)),
                               thread_name_prefix="replica-sync")

    def _admit(self) -> None:
        """Count one request in — or reject it if this replica is
        draining. The reject is a CLEAN typed error (the replica did no
        work): routers re-route it to a live replica without consuming
        the request's resume budget."""
        with self._m_lock:
            if self._draining:
                raise ray_tpu.exceptions.ReplicaDrainingError(
                    "replica is draining and no longer admits requests")
            self._ongoing += 1
            self._total += 1

    def _target(self, method: str):
        if self.is_function:
            return self.instance
        return getattr(self.instance, method or "__call__")

    async def handle_request(self, method: str, args, kwargs,
                             multiplexed_model_id: str = "",
                             request_ctx: Optional[Dict[str, Any]] = None):
        import asyncio
        import contextvars

        from ray_tpu.serve import context as serve_context
        from ray_tpu.serve import multiplex

        self._admit()
        token = multiplex._set_model_id(multiplexed_model_id)
        # The request context (request id + trace linkage) must be set
        # BEFORE copy_context() below so sync user code sees it in the
        # executor thread — same mechanism as the model id.
        rtoken = (serve_context._set_request_context(request_ctx)
                  if request_ctx is not None else None)
        try:
            target = self._target(method)
            if self._inspect.iscoroutinefunction(target):
                return await target(*args, **kwargs)
            # Sync user code: off the loop so it can't stall concurrent
            # requests. The context (multiplexed model id) rides along.
            ctx = contextvars.copy_context()
            return await asyncio.get_running_loop().run_in_executor(
                self._sync_pool, lambda: ctx.run(target, *args, **kwargs))
        finally:
            if rtoken is not None:
                serve_context._reset_request_context(rtoken)
            multiplex._reset_model_id(token)
            with self._m_lock:
                self._ongoing -= 1

    async def handle_request_streaming(self, method: str, args, kwargs,
                                       multiplexed_model_id: str = "",
                                       request_ctx: Optional[Dict[str,
                                                                  Any]] = None):
        """Streaming variant: each yield of the user method becomes one
        streamed item when called with num_returns="streaming" (reference:
        DeploymentResponseGenerator / RayServeHandle stream=True). Accepts
        async iterators, which this loop drives itself (an async
        generator; the engine deployments' token streams, which cost no
        thread), and sync generators, which get a pool thread each."""
        from ray_tpu.serve import context as serve_context
        from ray_tpu.serve import multiplex

        self._admit()
        token = multiplex._set_model_id(multiplexed_model_id)
        rtoken = (serve_context._set_request_context(request_ctx)
                  if request_ctx is not None else None)
        try:
            result = self._target(method)(*args, **kwargs)
            if hasattr(result, "__aiter__"):
                try:
                    async for item in result:
                        yield item
                finally:
                    # A consumer that left first (a client gone, a
                    # cancel): closing the iterator frees what it holds.
                    aclose = getattr(result, "aclose", None)
                    if aclose is not None:
                        await aclose()
            elif hasattr(result, "__next__"):
                async for batch in self._stream_sync_generator(result):
                    yield batch
            else:
                raise TypeError(
                    f"stream=True requires a generator; "
                    f"{method or '__call__'!r} returned "
                    f"{type(result).__name__}")
        finally:
            if rtoken is not None:
                serve_context._reset_request_context(rtoken)
            multiplex._reset_model_id(token)
            with self._m_lock:
                self._ongoing -= 1

    async def _stream_sync_generator(self, result):
        """A sync generator's items as :class:`StreamBatch` es (any
        deployment's sync generator method; the engine deployments
        return an async iterator instead and take no thread): ONE pool
        thread runs the generator for the stream's whole life (a slow
        producer cannot stall the replica's other requests; the copied
        context carries the multiplexed-model-id ContextVar into it, as
        on the non-streaming sync path) and leaves each item in a
        deque; the loop is woken once for however many lie there, and
        ships them as one object. Before PR 38 every item was a hop to a
        pool thread and back: at 256 open streams of 46 tokens a second
        the items queued behind those hops for minutes."""
        import asyncio
        import contextvars

        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        made: collections.deque = collections.deque()
        wake = asyncio.Event()
        stop = threading.Event()
        signalled = [False]

        def produce():
            try:
                while not stop.is_set():
                    try:
                        item = ctx.run(next, result)
                    except StopIteration:
                        item = _STREAM_OVER
                    except BaseException as e:  # noqa: BLE001 — re-raised
                        item = _StreamRaised(e)     # on the loop, in order
                    made.append(item)
                    # Appended BEFORE the flag is read: a consumer that
                    # has reset it drains after resetting, so it sees
                    # this item or is woken for it.
                    if not signalled[0]:
                        signalled[0] = True
                        loop.call_soon_threadsafe(wake.set)
                    if item is _STREAM_OVER or isinstance(item,
                                                          _StreamRaised):
                        return
            finally:
                if stop.is_set():
                    # The consumer left first (a client gone, a cancel):
                    # closing the generator runs its ``finally``.
                    result.close()

        loop.run_in_executor(self._sync_pool, produce)
        try:
            while True:
                await wake.wait()
                signalled[0] = False
                wake.clear()
                batch = StreamBatch()
                while made:
                    item = made.popleft()
                    if item is _STREAM_OVER or isinstance(item,
                                                          _StreamRaised):
                        if batch:
                            yield batch
                        if item is _STREAM_OVER:
                            return
                        raise item.error
                    batch.append(item)
                if batch:
                    yield batch
        finally:
            stop.set()

    @ray_tpu.method(concurrency_group=CONTROL_GROUP)
    def metrics(self):
        """Ongoing-request count the autoscaler averages (reference:
        replica metrics pushed to the controller, autoscaling_policy.py)."""
        with self._m_lock:
            return {"ongoing": self._ongoing, "total": self._total}

    @ray_tpu.method(concurrency_group=CONTROL_GROUP)
    def pressure(self):
        """Pressure snapshot for the serve pressure endpoint: router
        in-flight counts plus whatever the hosted callable reports (the
        continuous-batching deployments expose queue depth / KV blocks
        free / in-flight prefill tokens through their own ``pressure()``)."""
        with self._m_lock:
            out = {"ongoing": self._ongoing, "total": self._total}
        if not self.is_function:
            probe = getattr(self.instance, "pressure", None)
            if callable(probe):
                try:
                    out.update(probe() or {})
                except Exception:  # noqa: BLE001 — monitoring must not
                    pass           # fail requests' host process
        return out

    @ray_tpu.method(concurrency_group=CONTROL_GROUP)
    def health(self):
        return True

    @ray_tpu.method(concurrency_group=CONTROL_GROUP)
    def node_id(self):
        """The node hosting this replica — the controller's key for
        preemption-notice targeting (a notice naming a node drains that
        node's replicas instead of letting them be guillotined)."""
        try:
            return ray_tpu.get_runtime_context().get_node_id()
        except Exception:  # noqa: BLE001 — no runtime context: untargetable
            return ""

    @ray_tpu.method(concurrency_group=CONTROL_GROUP)
    async def drain(self, deadline_s: Optional[float] = None):
        """Controller-initiated graceful drain: stop admitting (new
        requests get a clean :class:`ReplicaDrainingError` reject and
        re-route), finish in-flight requests up to ``deadline_s``
        (default ``RAY_TPU_SERVE_DRAIN_S``), then report back so the
        controller tears this replica down. Async — in-flight requests
        keep executing on this actor's loop while the drain waits."""
        import asyncio

        from ray_tpu._private import chaos

        if deadline_s is None:
            deadline_s = float(os.environ.get("RAY_TPU_SERVE_DRAIN_S",
                                              "30"))
        with self._m_lock:
            self._draining = True
            remaining = self._ongoing
        t0 = time.monotonic()
        deadline = t0 + max(float(deadline_s), 0.0)
        while remaining > 0 and time.monotonic() < deadline:
            if chaos.enabled():
                # Death-while-draining chaos site: the host dies before
                # the drain completes — in-flight streams fall back to
                # the journal's resume path. delay_drain (serve_drain
                # site) instead stretches the wait: a slow quiesce under
                # which the pool arbiter's FREEING stage must hold.
                chaos.inject("serve_replica", phase="drain")
                chaos.inject("serve_drain")
            await asyncio.sleep(0.02)
            with self._m_lock:
                remaining = self._ongoing
        return {"drained": remaining <= 0,
                "waited_s": time.monotonic() - t0,
                "remaining": remaining}


class ServeController:
    """Reconciles deployment specs → replica actors and autoscales them."""

    def __init__(self):
        self.deployments: Dict[str, Dict[str, Any]] = {}
        self.replicas: Dict[str, List[Any]] = {}
        self._route_version: Dict[str, int] = {}
        # Shared router loads: name -> (ts, [ongoing per replica]).
        self._loads_cache: Dict[str, Any] = {}
        # Pressure snapshots: name -> (ts, [per-replica dicts]).
        self._pressure_cache: Dict[str, Any] = {}
        # autoscaler intent: name -> (desired, first_seen_monotonic)
        self._scale_intent: Dict[str, Any] = {}
        # Cumulative ingress-shed count seen at the last autoscale
        # decision per deployment (the policy scales on the DELTA).
        self._shed_seen: Dict[str, float] = {}
        self._pg_cleanups: Dict[str, list] = {}
        self._replica_birth: Dict[int, float] = {}
        # Draining replicas: name -> [{replica, ref, t0, deadline,
        # cause}]. Out of the routing table (get_routes/pressure only
        # see self.replicas) but not yet torn down: each entry's ``ref``
        # is the in-flight Replica.drain() call, and _advance_drains
        # kills the replica when it resolves (drained / died) or the
        # deadline lapses.
        self._draining: Dict[str, List[Dict[str, Any]]] = {}
        self._reconcile_lock = threading.Lock()
        self._stop = False
        # Preemption notices drain a node's replicas instead of letting
        # the kill guillotine their in-flight requests (the serve twin
        # of the train plane's JIT-save guards; same pubsub channel).
        from ray_tpu.checkpoint import preempt as _preempt

        def _on_preempt(notice: Dict[str, Any]) -> None:
            # Elastic control signals (capacity hints, world-target
            # asks) ride this channel but are the trainers' to latch.
            if notice.get("kind") == "capacity" or \
                    notice.get("world_target") is not None:
                return
            try:
                self._drain_for_preemption(notice)
            except Exception:  # noqa: BLE001 — drain is best-effort
                logger.exception("preemption drain failed")

        self._preempt_cb = _preempt.register_preempt_callback(_on_preempt)
        try:
            _preempt.ensure_listener()
        except Exception:  # noqa: BLE001
            pass
        threading.Thread(target=self._reconcile_loop, daemon=True).start()

    def deploy(self, name: str, cls_or_fn, init_args, init_kwargs,
               num_replicas: int, is_function: bool,
               max_concurrency: int,
               autoscaling_config: Optional[Dict[str, Any]] = None,
               placement_strategy: Optional[str] = None,
               ray_actor_options: Optional[Dict[str, Any]] = None) -> bool:
        cfg = None
        if autoscaling_config is not None or num_replicas == "auto":
            cfg = dict(DEFAULT_AUTOSCALING)
            cfg.update(autoscaling_config or {})
            num_replicas = cfg["min_replicas"]
        with self._reconcile_lock:
            # The swap must not race a reconcile in flight (the loop
            # thread would write its group into an orphaned spec dict).
            prev = self.deployments.get(name) or {}
            keep_group = prev.get("placement") == placement_strategy == \
                "COMPACT" and \
                prev.get("actor_options") == dict(ray_actor_options or {})
            self.deployments[name] = {
                "cls": cls_or_fn, "args": init_args, "kwargs": init_kwargs,
                "num_replicas": num_replicas, "is_function": is_function,
                "max_concurrency": max_concurrency, "autoscaling": cfg,
                # Deployment scheduler (reference: deployment_scheduler.py
                # compact placement): COMPACT gangs replicas onto as few
                # nodes as possible via a PACK placement group; SPREAD
                # spreads them with the min-utilization policy.
                "placement": placement_strategy,
                "actor_options": dict(ray_actor_options or {}),
                # A same-shape COMPACT redeploy inherits the group (its
                # reservation would otherwise leak unreachable); any
                # placement/resource change starts clean.
                "_pg": prev.get("_pg") if keep_group else None,
            }
            if prev.get("_pg") is not None and not keep_group:
                old_pg = prev["_pg"]
                # Old gang + group are torn down: replicas would otherwise
                # keep double-charging the cluster alongside the new ones.
                for r in self.replicas.get(name, []):
                    self._replica_birth.pop(id(r), None)
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
                self.replicas[name] = []
                try:
                    from ray_tpu.util import remove_placement_group

                    remove_placement_group(old_pg)
                except Exception:  # noqa: BLE001
                    pass
        self._reconcile_once(name)
        return True

    @staticmethod
    def _shed_total(name: str) -> float:
        """Cumulative ingress sheds for a deployment (pressure + tenant
        buckets), via the shared readback in metrics_defs. In-process
        registry: the local runtime hosts ingress and controller in one
        process; cluster deployments scale primarily on the queue/KV
        pressure signals."""
        from ray_tpu._private import metrics_defs as mdefs

        return mdefs.serve_shed_total(name)

    def _pressure_desired(self, name: str, cfg: Dict[str, Any],
                          current: int) -> tuple:
        """(desired, signal) under the pressure policy: the max over the
        ongoing-count target (reference: autoscaling_policy.py), the
        engine admission-queue target, arena starvation, and the
        ingress-shed delta — each signal reads the same per-replica
        pressure fan-out the router and dashboard already consume."""
        snaps = [s for s in self.get_replica_pressure(name)
                 if s and not s.get("unreachable")]
        ongoing = sum(float(s.get("ongoing") or 0) for s in snaps)
        desired = math.ceil(ongoing / max(cfg["target_ongoing_requests"],
                                          1e-9))
        signal = "ongoing"
        tq = float(cfg.get("target_queue_depth") or 0)
        if tq > 0:
            queue = sum(float(s.get("queue_depth") or 0) for s in snaps)
            d_q = math.ceil(queue / tq)
            if d_q > desired:
                desired, signal = d_q, "queue"
        if cfg.get("kv_starvation_upscale"):
            engines = [s for s in snaps
                       if float(s.get("kv_blocks_total") or 0) > 0]
            starved = [s for s in engines
                       if (float(s.get("kv_blocks_free") or 0)
                           + float(s.get("kv_blocks_cached") or 0)) <= 0]
            if engines and len(starved) == len(engines) and \
                    current + 1 > desired:
                # EVERY engine replica has nothing left to admit with:
                # one more replica, even when queue counters look calm.
                desired, signal = current + 1, "kv"
        tpt = float(cfg.get("target_prefill_queue_tokens") or 0)
        if tpt > 0:
            # Prefill-role fleets: waiting prompt tokens (admission
            # queue + parked handoffs) are the work unit, not request
            # count — one 4k-token prompt loads a replica like dozens
            # of short ones.
            ptoks = sum(float(s.get("prefill_queue_tokens") or 0)
                        for s in snaps)
            d_p = math.ceil(ptoks / tpt)
            if d_p > desired:
                desired, signal = d_p, "prefill_tokens"
        imp_floor = float(cfg.get("importable_floor") or 0)
        if imp_floor > 0:
            # Decode-role fleets: when EVERY engine's importable-block
            # headroom is under the floor, the next handoff's
            # reservation is about to fail — add a replica before the
            # transfer plane starts bouncing.
            engines = [s for s in snaps
                       if float(s.get("kv_blocks_total") or 0) > 0]
            low = [s for s in engines
                   if float(s.get("kv_blocks_importable") or 0)
                   < imp_floor]
            if engines and len(low) == len(engines) and \
                    current + 1 > desired:
                desired, signal = current + 1, "importable"
        if cfg.get("shed_upscale"):
            sheds = self._shed_total(name)
            last = self._shed_seen.setdefault(name, sheds)
            self._shed_seen[name] = sheds
            if sheds > last and current + 1 > desired:
                desired, signal = current + 1, "shed"
        return desired, signal

    def _autoscale_once(self, name: str):
        """Closed-loop replica scaling: desired comes from the pressure
        policy (ongoing count, engine queue depth, KV-arena starvation,
        shed rate), clamped to [min, max] and the pool arbiter's chip
        cap, applied after the respective upscale/downscale delay holds
        steadily. Scale-down always goes through the drain path
        (reconcile drains victims instead of killing)."""
        from ray_tpu._private import metrics_defs as mdefs

        spec = self.deployments.get(name)
        if spec is None or spec["autoscaling"] is None:
            return
        cfg = spec["autoscaling"]
        if not self.replicas.get(name, []):
            return
        current = spec["num_replicas"]
        desired, signal = self._pressure_desired(name, cfg, current)
        lo, hi = cfg["min_replicas"], cfg["max_replicas"]
        cap = spec.get("pool_cap")
        if cap is not None:
            # Chips leased away by the pool arbiter are a hard ceiling —
            # below min_replicas too: the arbiter's SLO guard is the
            # path back, not a tug-of-war with the reconciler.
            hi = min(hi, int(cap))
            lo = min(lo, hi)
        desired = max(lo, min(hi, desired))
        if desired == current:
            self._scale_intent.pop(name, None)
            return
        now = time.monotonic()
        intent = self._scale_intent.get(name)
        if intent is None or intent[0] != desired:
            self._scale_intent[name] = (desired, now)
            return
        delay = (cfg["upscale_delay_s"] if desired > current
                 else cfg["downscale_delay_s"])
        if now - intent[1] < delay:
            return
        with self._reconcile_lock:
            live = self.deployments.get(name)
            if live is not None:
                live["num_replicas"] = desired
        self._scale_intent.pop(name, None)
        mdefs.SERVE_AUTOSCALE_DECISIONS.inc(tags={
            "deployment": name,
            "direction": "up" if desired > current else "down",
            "signal": signal})
        # Flight-recorder root for the scale-down drains the reconcile
        # below starts (they cite this decision as their cause_event).
        scale_ev = _events.emit(
            "serve.autoscale", subject={"deployment": name},
            direction="up" if desired > current else "down",
            signal=signal, current=current, desired=desired)
        self._reconcile_once(name, cause_event=scale_ev)

    def _routes_changed(self, name: str) -> None:
        """Publish a new routing table version AND drop the controller's
        own loads/pressure caches for the deployment: they are arrays
        aligned per-index with the OLD table, and routers refetching
        after the event would otherwise be served the stale,
        index-misaligned snapshots for up to a TTL (mis-costing
        survivors / shedding on a removed replica's entry)."""
        self._loads_cache.pop(name, None)
        self._pressure_cache.pop(name, None)
        self._route_version[name] = self._route_version.get(name, 0) + 1
        _publish_route_event(name)

    DRAIN_GRACE_S = 2.0  # RPC slack past the replica's own deadline

    def _begin_drain(self, name: str, replica, cause: str,
                     cause_event: str = "") -> None:
        """Start one replica's graceful drain. The caller (under the
        reconcile lock) has already removed it from the routing table;
        this fires ``Replica.drain`` and parks the entry for
        :meth:`_advance_drains` to finish. A replica that cannot even be
        asked to drain is killed on the spot. ``cause_event`` links the
        flight-recorder record to what forced the drain (a preemption
        notice id, an autoscale decision)."""
        from ray_tpu._private import metrics_defs as mdefs

        deadline_s = float(os.environ.get("RAY_TPU_SERVE_DRAIN_S", "30"))
        replica_tag = f"{id(replica):x}"
        entry = {"replica": replica, "t0": time.monotonic(),
                 "deadline": time.monotonic() + deadline_s,
                 "cause": cause, "ref": None}
        try:
            entry["ref"] = replica.drain.remote(deadline_s)
        except Exception:  # noqa: BLE001 — undrainable: tear down now
            _events.emit("serve.drain_begin", cause=cause_event,
                         subject={"deployment": name,
                                  "replica": replica_tag},
                         drain_cause=cause, outcome="undrainable")
            try:
                ray_tpu.kill(replica)
            except Exception:  # noqa: BLE001
                pass
            return
        entry["event_id"] = _events.emit(
            "serve.drain_begin", cause=cause_event,
            subject={"deployment": name, "replica": replica_tag},
            drain_cause=cause, deadline_s=deadline_s)
        self._draining.setdefault(name, []).append(entry)
        mdefs.SERVE_REPLICA_DRAINS.inc(tags={"deployment": name,
                                             "cause": cause})

    def _advance_drains(self, name: str) -> None:
        """Finish drains whose Replica.drain resolved (drained, hit its
        deadline, or died mid-drain) — tear the replica down and record
        the drain duration by outcome. Requests still running when the
        deadline lapses are killed with the replica; their callers'
        journals resume them on a live replica (death-while-draining
        falls back to the resume path by design)."""
        # Claim the entries under the lock (a preempt callback or drain
        # RPC may append concurrently; an unlocked read-modify-write
        # here could drop their entry and leak the replica), process
        # outside it (the get below can block up to 1s), merge back.
        with self._reconcile_lock:
            entries = self._draining.pop(name, [])
        if not entries:
            return
        from ray_tpu._private import metrics_defs as mdefs

        now = time.monotonic()
        keep = []
        for e in entries:
            outcome = None
            try:
                ready, _ = ray_tpu.wait([e["ref"]], num_returns=1,
                                        timeout=0)
            except Exception:  # noqa: BLE001
                ready = []
            if ready:
                try:
                    res = ray_tpu.get(e["ref"], timeout=1)
                    outcome = ("drained" if res and res.get("drained")
                               else "deadline")
                except ray_tpu.exceptions.ActorDiedError:
                    outcome = "died"
                except Exception:  # noqa: BLE001
                    outcome = "deadline"
            elif now > e["deadline"] + self.DRAIN_GRACE_S:
                outcome = "deadline"
            if outcome is None:
                keep.append(e)
                continue
            mdefs.SERVE_DRAIN_SECONDS.observe(
                now - e["t0"], tags={"deployment": name,
                                     "outcome": outcome})
            _events.emit("serve.drain_end",
                         cause=e.get("event_id", ""),
                         subject={"deployment": name,
                                  "replica": f"{id(e['replica']):x}"},
                         outcome=outcome, drain_cause=e["cause"],
                         waited_s=now - e["t0"])
            if outcome == "died":
                mdefs.SERVE_REPLICA_DEATHS.inc(
                    tags={"deployment": name, "cause": "drain"})
            try:
                ray_tpu.kill(e["replica"])
            except Exception:  # noqa: BLE001
                pass
        if keep:
            with self._reconcile_lock:
                # EXTEND, never assign: entries appended while we were
                # processing must survive the merge.
                self._draining.setdefault(name, []).extend(keep)

    def _drain_for_preemption(self, notice: Dict[str, Any]) -> None:
        """A preemption notice for a node: drain that node's replicas
        (all replicas for an unscoped notice) instead of waiting for the
        host to kill them. The routing table drops them immediately;
        reconcile respawns replacements (checkpoint cold-start when the
        deployment was built with ``checkpoint_path``)."""
        target = str(notice.get("node", "*") or "*")
        drain_all = target in ("", "*", "all")
        # Phase 1, OUTSIDE the lock: probe replica node ids (up to ~2s
        # of remote waits — holding the reconcile lock through them
        # would freeze deploys and the very respawn work the preemption
        # deadline depends on). One shared fan-out across ALL
        # deployments (the get_replica_loads pattern).
        with self._reconcile_lock:
            snapshot = {name: list(reps)
                        for name, reps in self.replicas.items() if reps}
        hits_by_name: Dict[str, list] = {}
        if drain_all:
            hits_by_name = {n: list(reps) for n, reps in snapshot.items()}
        else:
            flat = [(name, r) for name, reps in snapshot.items()
                    for r in reps]
            refs = [r.node_id.remote() for _, r in flat]
            try:
                ready, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                        timeout=2.0)
                ready_ids = {r.id().binary() for r in ready}
                for (name, r), ref in zip(flat, refs):
                    if ref.id().binary() not in ready_ids:
                        continue
                    try:
                        nid = str(ray_tpu.get(ref, timeout=0.1) or "")
                    except Exception:  # noqa: BLE001
                        continue
                    if nid and (nid == target or nid.startswith(target)):
                        hits_by_name.setdefault(name, []).append(r)
            except Exception:  # noqa: BLE001
                hits_by_name = {}
        # Phase 2, under the lock: mutate the tables — re-checking
        # membership, since reconcile may have replaced a probed
        # replica while we waited.
        with self._reconcile_lock:
            for name, hits in hits_by_name.items():
                current = list(self.replicas.get(name, []))
                hits = [r for r in hits if r in current]
                if not hits:
                    continue
                stay = [r for r in current if r not in hits]
                for r in hits:
                    self._replica_birth.pop(id(r), None)
                    # The notice id is the drain's cause: the trainer's
                    # JIT save and the arbiter's mid-handoff handling
                    # record the same id, tying all three reactions to
                    # one preemption chain.
                    self._begin_drain(
                        name, r, cause="preemption",
                        cause_event=str(notice.get("notice_id", "")))
                self.replicas[name] = stay
                self._routes_changed(name)

    def drain_replicas(self, name: str, count: int = 1,
                       cause: str = "operator") -> int:
        """Operator/test surface: drain ``count`` replicas of ``name``
        out of rotation WITHOUT shrinking the spec — reconcile respawns
        replacements (a rolling replace). Returns how many drains
        started."""
        started = 0
        with self._reconcile_lock:
            current = list(self.replicas.get(name, []))
            while current and started < count:
                victim = current.pop()
                self._replica_birth.pop(id(victim), None)
                self._begin_drain(name, victim, cause=cause)
                started += 1
            if started:
                self.replicas[name] = current
                self._routes_changed(name)
        return started

    def draining_count(self, name: str) -> int:
        return len(self._draining.get(name, []))

    # ------------------------------------------------ chip-pool surface
    def pool_set_replicas(self, name: str, target: int,
                          cap: Optional[int] = None,
                          cause: str = "pool") -> Dict[str, Any]:
        """Pool-arbiter surface: set the deployment's replica target AND
        its chip cap in one step. Shrinks go through the drain path (the
        reconcile below drains victims); the cap clamps the pressure
        autoscaler so it cannot re-grow into chips leased away
        (``cap=None`` lifts the ceiling). Returns the previous state so
        a crashed-and-restarted arbiter can re-issue this idempotently."""
        with self._reconcile_lock:
            spec = self.deployments.get(name)
            if spec is None:
                raise ValueError(f"unknown deployment {name!r}")
            prev = {"target": spec["num_replicas"],
                    "cap": spec.get("pool_cap")}
            spec["num_replicas"] = max(int(target), 0)
            spec["pool_cap"] = None if cap is None else max(int(cap), 0)
        logger.info("pool: %s replicas -> %d (cap=%s, cause=%s)",
                    name, target, cap, cause)
        self._reconcile_once(name)
        return prev

    def pool_state(self, name: str) -> Dict[str, Any]:
        """One-RPC snapshot the arbiter confirms handoff stages against:
        routed (live, routable) replicas, the spec target, drains still
        in flight, and the chip cap."""
        spec = self.deployments.get(name) or {}
        return {"routed": len(self.replicas.get(name, [])),
                "target": spec.get("num_replicas", 0),
                "draining": len(self._draining.get(name, [])),
                "cap": spec.get("pool_cap")}

    def delete(self, name: str) -> bool:
        spec = self.deployments.pop(name, None)
        for r in self.replicas.pop(name, []):
            self._replica_birth.pop(id(r), None)
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass
        with self._reconcile_lock:
            doomed = self._draining.pop(name, [])
        for e in doomed:
            # Deleting a deployment is an explicit teardown: draining
            # replicas go down with it.
            try:
                ray_tpu.kill(e["replica"])
            except Exception:  # noqa: BLE001
                pass
        for cleanup in self._pg_cleanups.pop(name, []):
            cleanup()
        if spec is not None and spec.get("_pg") is not None:
            try:
                from ray_tpu.util import remove_placement_group

                remove_placement_group(spec["_pg"])
            except Exception:  # noqa: BLE001
                pass
        return True

    def get_replicas(self, name: str):
        return list(self.replicas.get(name, []))

    def get_routes(self, name: str):
        """(version, replicas) — versioned routing table (long-poll analog)."""
        return self._route_version.get(name, 0), \
            list(self.replicas.get(name, []))

    LOADS_TTL_S = 0.4

    def get_replica_loads(self, name: str):
        """Per-replica ongoing-request counts, aligned with get_routes
        order and TTL-cached controller-side (reference: the pow-2
        router's replica queue-length probes,
        ``replica_scheduler/pow_2_scheduler.py:813`` — centralized here so
        N ingress processes share ONE probe stream instead of N)."""
        now = time.monotonic()
        cached = self._loads_cache.get(name)
        if cached is not None and now - cached[0] < self.LOADS_TTL_S:
            return cached[1]
        replicas = list(self.replicas.get(name, []))
        refs = [r.metrics.remote() for r in replicas]
        # One SHARED deadline for the whole probe fan-out: serial
        # per-replica 1s timeouts made a deployment with several dying
        # replicas stall the controller (and every router waiting on it)
        # for N seconds per refresh.
        loads = [1 << 20] * len(refs)  # dying replica: avoid it
        try:
            ready, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                    timeout=1.0)
            ready_ids = {r.id().binary() for r in ready}
            for i, ref in enumerate(refs):
                if ref.id().binary() not in ready_ids:
                    continue
                try:
                    loads[i] = ray_tpu.get(ref, timeout=0.1)["ongoing"]
                except Exception:  # noqa: BLE001 — replica died mid-probe
                    pass
        except Exception:  # noqa: BLE001 — wait itself failed
            pass
        self._loads_cache[name] = (now, loads)
        return loads

    PRESSURE_TTL_S = 0.5

    def get_replica_pressure(self, name: str):
        """Per-replica pressure snapshots (queue depth, KV blocks free,
        in-flight prefill tokens from engine-backed replicas; router
        in-flight counts from every replica), aligned with get_routes
        order and TTL-cached — the prefix/KV-pressure router and the
        dashboard pressure endpoint both read this."""
        now = time.monotonic()
        cached = self._pressure_cache.get(name)
        if cached is not None and now - cached[0] < self.PRESSURE_TTL_S:
            return cached[1]
        replicas = list(self.replicas.get(name, []))
        refs = [r.pressure.remote() for r in replicas]
        # Shared deadline across the fan-out (same rationale as
        # get_replica_loads: dying replicas must not serialize stalls).
        out = [{"replica": i, "unreachable": True}
               for i in range(len(refs))]
        try:
            ready, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                    timeout=1.0)
            ready_ids = {r.id().binary() for r in ready}
            for i, ref in enumerate(refs):
                if ref.id().binary() not in ready_ids:
                    continue
                try:
                    snap = ray_tpu.get(ref, timeout=0.1)
                    out[i] = {"replica": i, **(snap or {})}
                except Exception:  # noqa: BLE001 — died mid-probe
                    pass
        except Exception:  # noqa: BLE001 — wait itself failed
            pass
        self._pressure_cache[name] = (now, out)
        return out

    def get_pressure(self):
        """Pressure for every deployment: {name: [per-replica dicts]}."""
        return {name: self.get_replica_pressure(name)
                for name in list(self.deployments)}

    def _publish_pressure(self) -> None:
        """Mirror the pressure snapshot into the GCS KV (``__serve__`` /
        ``pressure``) so the dashboard — which talks to the GCS, not to
        actors — can serve ``/api/v1/serve/pressure`` without a runtime."""
        core = _core()
        if not hasattr(core, "gcs") or not self.deployments:
            return
        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        pressure = self.get_pressure()
        body = json.dumps(pressure, sort_keys=True)
        now = time.monotonic()
        last_body, last_ts = getattr(self, "_pressure_published",
                                     (None, 0.0))
        # Unchanged data still republishes every few seconds so the
        # snapshot's ts stays a usable controller-liveness signal, but
        # an idle cluster doesn't churn the GCS KV (and its WAL) at the
        # reconcile cadence.
        if body == last_body and now - last_ts < 5.0:
            return
        self._pressure_published = (body, now)
        snap = {"ts": time.time(), "deployments": pressure}
        core.gcs.KvPut(pb.KvRequest(
            ns="__serve__", key="pressure",
            value=json.dumps(snap).encode(), overwrite=True))

    def list_deployments(self):
        return {name: {"num_replicas": spec["num_replicas"]}
                for name, spec in self.deployments.items()}

    def _reconcile_once(self, name: str, cause_event: str = ""):
        # Slow placement-group creation happens OUTSIDE the lock (a 30s
        # wait under it would freeze every deployment's maintenance);
        # the lock then only covers fast state transitions.
        self._maybe_prepare_compact_group(name)
        # One reconcile at a time: the deploy RPC thread and the loop
        # thread would otherwise race group creation / replica lists
        # (last-write-wins leaks the loser's group and replicas).
        with self._reconcile_lock:
            self._reconcile_locked(name, cause_event=cause_event)

    def _compact_needs_grow(self, spec) -> bool:
        pg = spec.get("_pg")
        if time.monotonic() < spec.get("_pg_backoff", 0.0):
            return False
        if pg is None:
            return True
        if len(pg.bundle_specs) < spec["num_replicas"]:
            return True
        # Bundle SHAPE changes (bigger replicas) need a regrow too — the
        # old bundles could never admit the new demand.
        want = self._replica_bundle(spec.get("actor_options"))
        return spec.get("_pg_bundle") != want

    def _maybe_prepare_compact_group(self, name: str) -> None:
        from ray_tpu.util import placement_group, remove_placement_group

        with self._reconcile_lock:
            spec = self.deployments.get(name)
            if spec is None or spec.get("placement") != "COMPACT" or \
                    not self._compact_needs_grow(spec):
                return
            per_replica = self._replica_bundle(spec.get("actor_options"))
            want_replicas = spec["num_replicas"]
        new_pg = placement_group([dict(per_replica)] * want_replicas,
                                 strategy="PACK")
        placed = new_pg.wait(30)
        with self._reconcile_lock:
            spec = self.deployments.get(name)
            still_needed = (
                spec is not None and spec.get("placement") == "COMPACT"
                and self._compact_needs_grow(spec)
                and spec["num_replicas"] <= want_replicas
                and self._replica_bundle(
                    spec.get("actor_options")) == per_replica)
            if not placed or not still_needed:
                try:
                    remove_placement_group(new_pg)
                except Exception:  # noqa: BLE001
                    pass
                if spec is not None and not placed:
                    # Infeasible now: keep serving on the old group (if
                    # any) and retry later instead of thrashing.
                    spec["_pg_backoff"] = time.monotonic() + 30.0
                return
            old = spec.get("_pg")
            if old is not None:
                spec["_migrate"] = True

                def _cleanup(old=old):
                    try:
                        remove_placement_group(old)
                    except Exception:  # noqa: BLE001
                        pass

                self._pg_cleanups.setdefault(name, []).append(_cleanup)
            spec["_pg"] = new_pg
            spec["_pg_bundle"] = per_replica

    def _reconcile_locked(self, name: str, cause_event: str = ""):
        spec = self.deployments.get(name)
        if spec is None:
            return
        replica_cls = ray_tpu.remote(Replica)
        current = self.replicas.setdefault(name, [])
        # Remove dead replicas (probe with a cheap health call) — but a
        # replica still STARTING (worker spawn + placement-group bundle
        # admission can take many seconds) must not be declared dead by a
        # 2s probe, or the reconciler churns forever: each dropped-but-
        # actually-starting replica still holds its bundle, so every
        # replacement starves on pg-wait.
        now = time.monotonic()
        live = []
        for r in current:
            try:
                ray_tpu.get(r.health.remote(),
                            timeout=self.HEALTH_PROBE_TIMEOUT_S)
                live.append(r)
                self._replica_birth.pop(id(r), None)  # confirmed up
            except ray_tpu.exceptions.ActorDiedError:
                # Confirmed dead: replace immediately (no grace).
                self._replica_birth.pop(id(r), None)
                from ray_tpu._private import metrics_defs as mdefs

                mdefs.SERVE_REPLICA_DEATHS.inc(
                    tags={"deployment": name, "cause": "died"})
            except Exception:  # noqa: BLE001 — timeout: starting, busy OR dead
                # A replica that HAD answered and now misses a probe is
                # busy (a full engine and a hundred open streams can hold
                # a 2s probe off) or hung: it gets the same grace, from
                # its first missed probe. Dropped at once it would leave
                # the routing table while alive and holding its chip, and
                # its replacement could never start.
                birth = self._replica_birth.setdefault(id(r), now)
                if now - birth < self.REPLICA_STARTUP_GRACE_S:
                    live.append(r)  # starting or busy: keep, don't churn
                else:
                    # No answer for the whole grace: it is replaced, so
                    # it must also die. Left alive it keeps its resources
                    # — a chip is exclusive, so the replacement would
                    # wait on it forever while nothing routes to it.
                    self._replica_birth.pop(id(r), None)
                    logger.warning(
                        "serve: replica of %s answered no health probe for "
                        "%.0fs; killing and replacing it", name,
                        self.REPLICA_STARTUP_GRACE_S)
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001 — already gone
                        pass
        current = live
        opts: Dict[str, Any] = dict(spec.get("actor_options") or {})
        opts["max_concurrency"] = spec["max_concurrency"]
        opts["concurrency_groups"] = {CONTROL_GROUP: CONTROL_CONCURRENCY}
        placement = spec.get("placement")
        if placement == "COMPACT":
            strategy, regrown = self._compact_group_strategy(name, spec)
            if strategy is None:
                # No feasible group yet: keep whatever runs (but still
                # push routing if the live set shrank), retry later.
                changed = [id(r) for r in current] != \
                    [id(r) for r in self.replicas.get(name, [])]
                self.replicas[name] = current
                if changed:
                    self._routes_changed(name)
                return
            opts["scheduling_strategy"] = strategy
            if regrown:
                # Migrate: the whole gang restarts inside the new group so
                # compactness holds for ALL replicas, then the old group's
                # reservation is released (even when no replica was live —
                # a dead gang's old group must not hold reservations).
                for r in current:
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
                    self._replica_birth.pop(id(r), None)
                current = []
                for cleanup in self._pg_cleanups.pop(name, []):
                    cleanup()
        elif placement == "SPREAD":
            opts["scheduling_strategy"] = "SPREAD"
        while len(current) < spec["num_replicas"]:
            replica = replica_cls.options(**opts).remote(
                spec["cls"], spec["args"], spec["kwargs"],
                spec["is_function"],
                sync_workers=spec["max_concurrency"])
            self._replica_birth[id(replica)] = time.monotonic()
            current.append(replica)
        while len(current) > spec["num_replicas"]:
            # Scale-down DRAINS the victim instead of killing it: it
            # leaves the routing table now (the publish below), stops
            # admitting, finishes its in-flight requests up to
            # RAY_TPU_SERVE_DRAIN_S, and _advance_drains tears it down.
            victim = current.pop()
            self._replica_birth.pop(id(victim), None)
            self._begin_drain(name, victim, cause="scale_down",
                              cause_event=cause_event)
        changed = [id(r) for r in current] != \
            [id(r) for r in self.replicas.get(name, [])]
        self.replicas[name] = current
        if changed:
            # Push the new routing table to every handle (reference:
            # LongPollHost notify, long_poll.py:204).
            self._routes_changed(name)

    REPLICA_STARTUP_GRACE_S = 60.0
    # A probe crosses the replica's loop, which hundreds of open streams
    # share: at 256 streams a turn of that loop took 1.3 s and more (PR
    # 38), a 2 s probe missed for a minute on end, and the controller
    # killed a replica that was serving 12,000 tokens a second.
    HEALTH_PROBE_TIMEOUT_S = 10.0

    @staticmethod
    def _replica_bundle(actor_options: Dict[str, Any]) -> Dict[str, float]:
        """The full resource demand of one replica (TPU serving is the
        flagship case — CPU-only bundles could never admit it)."""
        opts = actor_options or {}
        bundle: Dict[str, float] = {"CPU": float(
            opts.get("num_cpus", 1) or 1)}
        if opts.get("num_gpus"):
            bundle["GPU"] = float(opts["num_gpus"])
        if opts.get("num_tpus"):
            bundle["TPU"] = float(opts["num_tpus"])
        if opts.get("memory"):
            bundle["memory"] = float(opts["memory"])
        for k, v in (opts.get("resources") or {}).items():
            bundle[k] = float(v)
        return bundle

    def _compact_group_strategy(self, name: str, spec):
        """Hand back the deployment's group strategy (the group itself is
        prepared outside the lock by _maybe_prepare_compact_group); the
        regrown flag is a one-shot migration marker."""
        from ray_tpu.util import PlacementGroupSchedulingStrategy

        pg = spec.get("_pg")
        if pg is None:
            return None, False  # nowhere to place yet; retry next tick
        return PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=-1), \
            spec.pop("_migrate", False)

    def _reconcile_loop(self):
        from ray_tpu._private import worker as worker_mod

        while not self._stop:
            time.sleep(0.5)
            if worker_mod.global_worker_or_none() is None:
                # The hosting runtime is gone (ray_tpu.shutdown() with
                # this controller's stop RPC lost/raced): this thread is
                # orphaned. Exit instead of letting the maintenance work
                # below lazily AUTO-INITIALIZE a fresh runtime through
                # global_worker() — a zombie controller quietly owning a
                # new runtime is far worse than a missed tick.
                return
            for name in list(self.deployments):
                try:
                    self._autoscale_once(name)
                    self._reconcile_once(name)
                except Exception:  # noqa: BLE001
                    pass
            # Advance drains for every deployment with one in flight —
            # including names no longer in the spec map (a redeploy
            # mid-drain must not leak the old replica).
            for name in list(self._draining):
                try:
                    self._advance_drains(name)
                except Exception:  # noqa: BLE001
                    pass
            try:
                self._publish_pressure()
            except Exception:  # noqa: BLE001
                pass

    def shutdown(self):
        self._stop = True
        try:
            from ray_tpu.checkpoint import preempt as _preempt

            _preempt.unregister_preempt_callback(self._preempt_cb)
        except Exception:  # noqa: BLE001
            pass
        for name in list(self.deployments):
            self.delete(name)
        with self._reconcile_lock:
            leftovers = [e for entries in self._draining.values()
                         for e in entries]
            self._draining.clear()
        for e in leftovers:
            try:
                ray_tpu.kill(e["replica"])
            except Exception:  # noqa: BLE001
                pass


class DeploymentResponse:
    """Future-like response (reference: ``DeploymentResponse``)."""

    def __init__(self, ref, handle: Optional["DeploymentHandle"] = None,
                 call: Optional[tuple] = None, replica: Any = None):
        self._ref = ref
        self._handle = handle
        self._call = call
        self._replica = replica
        # Minted lazily at the FIRST retry: the clean unary path does no
        # per-request id work (with tracing off it must stay free), but a
        # re-routed/resubmitted request needs a stable subject key so its
        # flight-recorder resume events chain under one request id.
        self._request_id = ""

    def _note_flight_resume(self, mode: str, replica=None) -> None:
        name = self._handle._name
        if not self._request_id:
            self._request_id = uuid.uuid4().hex[:16]
        # Best-effort cause inference (in-process rings only). Prefer
        # THE rejecting replica's own drain record: a sibling drain (a
        # scale-down racing a preemption) can be newer but causally
        # unrelated — deployment-newest would misattribute the resume.
        # Fallbacks: the newest drain for the deployment, then the
        # newest injection/drain anywhere (the trigger observed an
        # effect — a reject, a dead replica — without its event id).
        cause = ""
        if replica is not None:
            cause = _events.latest_event_id(
                ["serve.drain_begin"],
                subject={"deployment": name,
                         "replica": f"{id(replica):x}"})
        cause = cause or _events.latest_event_id(
            ["serve.drain_begin"], subject={"deployment": name}) or \
            _events.latest_event_id(["serve.drain_begin", "chaos.inject"])
        _events.emit("serve.resume", cause=cause,
                     subject={"deployment": name,
                              "request_id": self._request_id},
                     mode=mode)

    def result(self, timeout_s: Optional[float] = 60.0):
        from ray_tpu.serve import recovery

        ref, replica = self._ref, self._replica
        resumes = 0
        drain_rejects = 0
        while True:
            try:
                out = ray_tpu.get(ref, timeout=timeout_s)
                if resumes and self._handle is not None:
                    # The call completed only thanks to >=1 death
                    # retry: tagged so the outcome counter separates
                    # clean finishes from recovered ones.
                    recovery.note_unary_resumed(self._handle._name,
                                                self._handle._model_id)
                return out
            except ray_tpu.exceptions.ReplicaDrainingError:
                # Clean reject — the draining replica did no work, so
                # the re-route is free (no resume budget). Bounded by
                # the shared cap via the eviction below.
                if self._handle is None or self._call is None or \
                        drain_rejects >= recovery.DRAIN_REJECT_CAP:
                    raise
                drain_rejects += 1
                recovery.note_unary_retry(self._handle._name,
                                          "drain_reject")
                self._note_flight_resume("drain_reject", replica)
                self._handle._evict(replica)
                args, kwargs = self._call
                retry = self._handle.remote(*args, **kwargs)
                ref, replica = retry._ref, retry._replica
            except ray_tpu.exceptions.ActorDiedError as e:
                # The chosen replica died mid-flight. A unary call's
                # journal is its immutable (args, kwargs) submission
                # plus the fact that ZERO response bytes were delivered
                # — resubmission cannot double-deliver, so the retry is
                # safe; it is still budgeted (RAY_TPU_SERVE_MAX_RESUMES,
                # not a blind fixed cap) and tagged, and exhaustion is a
                # typed terminal error (reference: router retries on
                # ActorDiedError with an updated replica set).
                if self._handle is None or self._call is None:
                    raise
                if resumes >= recovery.max_resumes():
                    recovery.note_unary_exhausted(self._handle._name,
                                                  self._handle._model_id)
                    raise recovery.exhausted_error(
                        self._handle._name, resumes) from e
                resumes += 1
                recovery.note_unary_retry(self._handle._name, "resubmit")
                self._note_flight_resume("resubmit", replica)
                self._handle._evict(replica)
                args, kwargs = self._call
                retry = self._handle.remote(*args, **kwargs)
                ref, replica = retry._ref, retry._replica

    @property
    def ref(self):
        return self._ref


class DeploymentResponseGenerator:
    """Iterates the values of a streaming deployment call as the replica
    yields them (reference: ``DeploymentResponseGenerator`` — handle
    ``stream=True``). Wraps the core ObjectRefGenerator.
    ``per_item_timeout_s`` bounds each item (None = wait indefinitely;
    task failure still surfaces through the stream's stored error).
    Carries the serving ``_replica`` so the recovery plane
    (serve/recovery.py) can evict it from the routing table when the
    stream dies mid-flight."""

    def __init__(self, obj_ref_gen, per_item_timeout_s=None,
                 replica: Any = None):
        self._gen = obj_ref_gen
        self._timeout = per_item_timeout_s
        self._replica = replica
        # Items of a StreamBatch not yet handed out.
        self._held: collections.deque = collections.deque()

    def __iter__(self):
        return self

    def __next__(self):
        while not self._held:
            ref = (next(self._gen) if self._timeout is None
                   else self._gen._next_internal(self._timeout))
            item = ray_tpu.get(ref, timeout=self._timeout)
            if not isinstance(item, StreamBatch):
                return item
            self._held.extend(item)
        return self._held.popleft()

    def ready(self) -> bool:
        """Whether ``next()`` would return an item without waiting."""
        return bool(self._held) or self._gen.ready()


# Process-wide in-flight request counts per deployment: the queue-depth
# gauge must aggregate across every handle to a deployment (independent
# get_handle() calls have separate router states, and a per-handle sum
# would overwrite the series last-writer-wins).
_QUEUE_DEPTH: Dict[str, int] = {}
_QUEUE_DEPTH_LOCK = threading.Lock()


def _queue_depth_delta(deployment: str, delta: int) -> int:
    with _QUEUE_DEPTH_LOCK:
        depth = max(_QUEUE_DEPTH.get(deployment, 0) + delta, 0)
        _QUEUE_DEPTH[deployment] = depth
    return depth


class _RouterState:
    """Routing table + subscription shared by a handle and its clones."""

    def __init__(self):
        self.replicas: List[Any] = []
        self.dirty = True
        self.inflight: Dict[int, int] = {}
        self.lock = threading.Lock()
        self.subscribed = False
        # Cluster-wide per-replica load baseline from the controller
        # (other callers' traffic); local inflight rides on top.
        self.shared_loads: List[int] = []
        self.loads_ts = 0.0
        # Controller-published per-replica PRESSURE snapshots (engine
        # queue depth, KV blocks free/cached, in-flight prefill tokens),
        # TTL-cached per router: the prefix-affinity policy and the
        # ingress admission gate read the cached copy instead of paying
        # the controller's poll per request.
        self.shared_pressure: List[Dict[str, Any]] = []
        self.pressure_ts = 0.0


def _affinity_candidates(prefix_key: str, n: int) -> List[int]:
    """Rendezvous (highest-random-weight) hashing of a prefix
    fingerprint over the replica set: a stable per-key preference order
    that barely reshuffles when the replica count changes. The top TWO
    candidates are the key's home and spill replicas — a hot prefix
    concentrates on at most two KV caches instead of melting one.
    blake2b, not crc32: CRC is affine, so keys differing in a suffix
    byte order the replicas identically and every home collapses onto
    one replica."""
    import hashlib

    def weight(i: int) -> bytes:
        return hashlib.blake2b(f"{prefix_key}:{i}".encode(),
                               digest_size=8).digest()

    order = sorted(range(n), key=weight, reverse=True)
    return order[:2] if n >= 2 else order


def _pressure_cost(snap: Optional[Dict[str, Any]], local_inflight: int,
                   hot: float) -> float:
    """Congestion score for one replica: router in-flight + engine queue
    depth, plus a hot-sized penalty when the paged-KV arena has nothing
    left to admit with (free or reclaimable) — an arena-starved replica
    is as bad as a deep queue even when its router counters look calm.
    Unreachable/missing snapshots fall back to the local view only."""
    cost = float(local_inflight)
    if not snap or snap.get("unreachable"):
        return cost
    cost += float(snap.get("queue_depth") or 0)
    cost += float(snap.get("ongoing") or 0)
    total = snap.get("kv_blocks_total") or 0
    if total:
        avail = ((snap.get("kv_blocks_free") or 0)
                 + (snap.get("kv_blocks_cached") or 0))
        if avail <= 0:
            cost += hot
    return cost


def _affinity_pick(prefix_key: str, n: int,
                   pressure: List[Dict[str, Any]],
                   inflight: Dict[int, int],
                   hot: Optional[float] = None) -> tuple:
    """Choose a replica for a prefix-keyed request: stay on the key's
    rendezvous home while it is healthy (below the ``hot`` congestion
    threshold, or no worse than the spill candidate), else spill to the
    second rendezvous choice. Returns ``(index, decision)`` with
    decision in {"affinity", "overflow"}."""
    if hot is None:
        hot = float(os.environ.get("RAY_TPU_AFFINITY_HOT_COST", "8"))
    cands = _affinity_candidates(prefix_key, n)
    if len(cands) == 1:
        return cands[0], "affinity"
    c0, c1 = cands

    def cost(i):
        return _pressure_cost(pressure[i] if i < len(pressure) else None,
                              inflight.get(i, 0), hot)

    if cost(c0) < hot or cost(c0) <= cost(c1):
        return c0, "affinity"
    return c1, "overflow"


class DeploymentHandle:
    """Routes calls to replicas. The routing table is *pushed*: a subscriber
    registered on first use refreshes it when the controller publishes a
    route-change event (reference: long-poll updates, ``long_poll.py:204``)
    — no per-call TTL polling. A call that raced a replica death refreshes
    immediately and retries on a live replica."""

    def __init__(self, deployment_name: str, method_name: Optional[str] = None,
                 _router: Optional["_RouterState"] = None,
                 _stream: bool = False, _model_id: str = "",
                 _request_ctx: Optional[Dict[str, Any]] = None,
                 _prefix_key: str = ""):
        self._name = deployment_name
        self._method = method_name
        self._stream = _stream
        self._model_id = _model_id
        # Prefix fingerprint (hash of the first block-aligned prompt
        # chunks, minted at the ingress): routes the call to the replica
        # most likely to hold the prefix in its radix KV cache, tempered
        # by replica pressure. "" = no affinity (pow-2 balancing).
        self._prefix_key = _prefix_key
        # Per-call request context (request id + trace linkage, minted
        # at the ingress): ships to the replica so engine lifecycle
        # spans connect to the caller's trace. None = mint on demand
        # when tracing is enabled.
        self._request_ctx = _request_ctx
        # Router state (replica table, in-flight counts, subscription) is
        # SHARED across options()/method clones: one subscription per
        # logical handle, not per call.
        self._router = _router or _RouterState()

    def __reduce__(self):
        # Handles ship inside composed deployments' init args (reference:
        # build_app injects handles for nested bound deployments); router
        # state (locks, subscriptions, counts) is rebuilt per process,
        # call options (stream/model-id) survive the trip.
        return (_rebuild_handle,
                (self._name, self._method, self._stream, self._model_id))

    def options(self, method_name: Optional[str] = None, *,
                stream: Optional[bool] = None,
                multiplexed_model_id: Optional[str] = None,
                request_context: Optional[Dict[str, Any]] = None,
                prefix_key: Optional[str] = None) -> "DeploymentHandle":
        return DeploymentHandle(
            self._name,
            method_name if method_name is not None else self._method,
            _router=self._router,
            _stream=self._stream if stream is None else stream,
            _model_id=(self._model_id if multiplexed_model_id is None
                       else multiplexed_model_id),
            _request_ctx=(self._request_ctx if request_context is None
                          else request_context),
            _prefix_key=(self._prefix_key if prefix_key is None
                         else prefix_key))

    @property
    def _replicas(self):
        return self._router.replicas

    @property
    def _lock(self):
        return self._router.lock

    @property
    def _inflight(self):
        return self._router.inflight

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return _HandleMethod(self, name)

    def _ensure_subscribed(self):
        st = self._router
        if st.subscribed:
            return
        st.subscribed = True

        def on_event(name: str):
            if name == self._name:
                with st.lock:
                    st.dirty = True
                    # The replica set changed (death, drain, scale):
                    # per-index load/pressure snapshots are aligned with
                    # the OLD table — invalidate them so the next read
                    # refetches instead of mis-costing shifted indices
                    # (or shedding on a drained replica's stale entry).
                    st.loads_ts = 0.0
                    st.pressure_ts = 0.0
                    st.shared_loads = []
                    st.shared_pressure = []

        try:
            _subscribe_route_events(on_event)
        except Exception:  # noqa: BLE001
            pass

    def _refresh(self, force: bool = False):
        self._ensure_subscribed()
        st = self._router
        if not force and not st.dirty and st.replicas:
            return
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        _, replicas = ray_tpu.get(
            controller.get_routes.remote(self._name), timeout=30)
        with st.lock:
            changed = [id(r) for r in replicas] != \
                [id(r) for r in st.replicas]
            st.replicas = replicas
            st.dirty = False
            st.inflight = {}
            if changed:
                # New table: index-aligned caches are stale (see the
                # route-event callback above).
                st.loads_ts = 0.0
                st.pressure_ts = 0.0
                st.shared_loads = []
                st.shared_pressure = []

    def _evict(self, replica) -> None:
        """Drop a replica observed dead or draining; refreshed tables
        re-add the live set (reference: router removes failed replicas
        eagerly)."""
        st = self._router
        with st.lock:
            st.replicas = [r for r in st.replicas if r is not replica]
            st.inflight = {}
            st.dirty = not st.replicas
            # Its load/pressure entries must not cost the survivors
            # (indices shifted) or feed the admission gate.
            st.loads_ts = 0.0
            st.pressure_ts = 0.0
            st.shared_loads = []
            st.shared_pressure = []

    def _choose(self, model_id: str = "", prefix_key: str = ""):
        """Power-of-two-choices over in-flight counts; multiplexed calls
        instead hash the model id over the replica set so one model's
        requests keep hitting the replica whose LRU already holds it
        (reference: model-locality routing in serve/_private/multiplex).
        Prefix-keyed calls route by rendezvous-hashed PREFIX AFFINITY
        tempered by replica pressure: the request lands on the replica
        most likely to hold its prompt prefix in the radix KV cache,
        unless that replica is congested — then it spills to the key's
        second rendezvous choice so a hot prefix cannot melt one
        replica."""
        from ray_tpu._private import metrics_defs as mdefs

        self._refresh()
        if not self._replicas:
            # A fresh deployment may still be starting replicas.
            deadline = time.monotonic() + 10.0
            while not self._replicas and time.monotonic() < deadline:
                time.sleep(0.05)
                self._refresh(force=True)
        if not self._replicas:
            raise RuntimeError(f"deployment {self._name!r} has no replicas")
        shared: List[int] = []
        pressure: List[Dict[str, Any]] = []
        if not model_id and len(self._replicas) > 1:
            if prefix_key:
                pressure = self._fetch_shared_pressure()
            else:
                shared = self._fetch_shared_loads()
        with self._lock:
            if model_id:
                import zlib

                idx = zlib.crc32(model_id.encode()) % len(self._replicas)
            elif len(self._replicas) == 1:
                idx = 0
            elif prefix_key:
                idx, decision = _affinity_pick(
                    prefix_key, len(self._replicas), pressure,
                    self._inflight)
                mdefs.SERVE_ROUTER_AFFINITY.inc(
                    tags={"deployment": self._name, "decision": decision})
            else:
                # Pow-2 over shared (cluster-wide) + local in-flight: N
                # independent ingress processes see each other's load
                # through the controller baseline instead of each assuming
                # idle replicas (reference: pow_2_scheduler.py:813).
                loads = shared if len(shared) == len(self._replicas) \
                    else None
                a, b = random.sample(range(len(self._replicas)), 2)

                def cost(i):
                    return (loads[i] if loads else 0) + \
                        self._inflight.get(i, 0)

                idx = a if cost(a) <= cost(b) else b
            self._inflight[idx] = self._inflight.get(idx, 0) + 1
        return idx, self._replicas[idx]

    LOADS_TTL_S = 0.5

    def _fetch_shared_loads(self) -> List[int]:
        """Controller-published per-replica queue depth, TTL-cached per
        router (one fetch per 0.5s under load, amortized over calls)."""
        st = self._router
        now = time.monotonic()
        if now - st.loads_ts < self.LOADS_TTL_S:
            return st.shared_loads
        st.loads_ts = now  # claim the slot first: no thundering herd
        try:
            controller = ray_tpu.get_actor(CONTROLLER_NAME)
            loads = list(ray_tpu.get(
                controller.get_replica_loads.remote(self._name), timeout=5))
        except Exception:  # noqa: BLE001 — fall back to local-only view
            loads = []
        with st.lock:
            st.shared_loads = loads
        return loads

    PRESSURE_TTL_S = 0.5

    def _fetch_shared_pressure(self) -> List[Dict[str, Any]]:
        """Per-replica pressure snapshots (engine queue depth, KV blocks
        free/cached, in-flight prefill tokens), TTL-cached per router —
        the freshness path: routing and ingress admission read the
        CACHED copy; only one call per TTL pays the controller round
        trip (which itself serves from its own 0.5s probe cache), so
        per-request cost is a clock read and a dict lookup. Subscribes
        to route events so a replica removal (death/drain) invalidates
        the cache even on gate-only paths that never route."""
        from ray_tpu._private import chaos

        self._ensure_subscribed()
        st = self._router
        now = time.monotonic()
        if now - st.pressure_ts < self.PRESSURE_TTL_S:
            return st.shared_pressure
        if chaos.enabled():
            # Dropped/stale pressure fetch: keep serving whatever the
            # cache holds (possibly nothing) without refreshing — the
            # admission gate and affinity policy must stay safe on
            # stale data.
            d = chaos.inject("serve_pressure", deployment=self._name)
            if d and d.get("drop"):
                return st.shared_pressure
        st.pressure_ts = now  # claim first: no thundering herd
        try:
            controller = ray_tpu.get_actor(CONTROLLER_NAME)
            snaps = list(ray_tpu.get(
                controller.get_replica_pressure.remote(self._name),
                timeout=5))
        except Exception:  # noqa: BLE001 — no controller: empty view
            snaps = []
        with st.lock:
            st.shared_pressure = snaps
        return snaps

    def _observe_done(self, start: float) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        mdefs.SERVE_LATENCY.observe(time.monotonic() - start,
                                    tags={"deployment": self._name})
        mdefs.SERVE_QUEUE_DEPTH.set(_queue_depth_delta(self._name, -1),
                                    tags={"deployment": self._name})

    def remote(self, *args, **kwargs):
        from ray_tpu.util import tracing

        if not tracing.enabled():
            # Hot path with tracing off: one env check, no context work.
            return self._remote_impl(args, kwargs, self._request_ctx)
        rctx = self._request_ctx
        if rctx is None:
            # Direct handle call (no ingress): mint the request identity
            # here, continuing the caller's trace when one is active.
            cur = tracing.current()
            rctx = {"request_id": tracing.gen_id(),
                    "trace_id": cur[0] if cur else tracing.gen_id(),
                    "parent_span_id": cur[1] if cur else "",
                    "deployment": self._name, "tenant": self._model_id}
        parent = rctx.get("parent_span_id", "")
        # Pre-allocate the route span id so the engine's lifecycle spans
        # (emitted from the replica long after this returns) can parent
        # to it; the span itself closes when dispatch completes.
        route_span = tracing.gen_id()
        # ``route_ts``: the replica closes ``serve.hop`` (here to its
        # method's entry) against it; every path to a replica, resumes
        # included, passes through here.
        rctx = {**rctx, "parent_span_id": route_span,
                "route_ts": time.time()}
        with tracing.explicit_span(
                "serve.route", trace_id=rctx.get("trace_id", ""),
                span_id=route_span, parent_span_id=parent, kind="route",
                request_id=rctx.get("request_id", ""),
                deployment=self._name):
            return self._remote_impl(args, kwargs, rctx)

    def _remote_impl(self, args, kwargs, request_ctx):
        from ray_tpu._private import metrics_defs as mdefs

        idx, replica = self._choose(self._model_id, self._prefix_key)
        mdefs.SERVE_REQUESTS.inc(tags={"deployment": self._name})
        mdefs.SERVE_QUEUE_DEPTH.set(_queue_depth_delta(self._name, +1),
                                    tags={"deployment": self._name})
        start = time.monotonic()
        if self._stream:
            gen = replica.handle_request_streaming.options(
                num_returns="streaming").remote(
                self._method, args, kwargs, self._model_id, request_ctx)

            def _sdone(_fut):
                with self._lock:
                    self._inflight[idx] = max(
                        self._inflight.get(idx, 1) - 1, 0)
                self._observe_done(start)

            try:
                gen.completed().future().add_done_callback(_sdone)
            except Exception:  # noqa: BLE001
                _sdone(None)
            return DeploymentResponseGenerator(gen, replica=replica)
        ref = replica.handle_request.remote(self._method, args, kwargs,
                                            self._model_id, request_ctx)

        def _done(_fut):
            with self._lock:
                self._inflight[idx] = max(self._inflight.get(idx, 1) - 1, 0)
            self._observe_done(start)

        try:
            ref.future().add_done_callback(_done)
        except Exception:  # noqa: BLE001
            from ray_tpu._private import metrics_defs as mdefs

            with self._lock:
                self._inflight[idx] = max(self._inflight.get(idx, 1) - 1, 0)
            # Balance the queue-depth gauge: the done callback that would
            # normally decrement it will never fire.
            mdefs.SERVE_QUEUE_DEPTH.set(_queue_depth_delta(self._name, -1),
                                        tags={"deployment": self._name})
        return DeploymentResponse(ref, handle=self, call=(args, kwargs),
                                  replica=replica)


def _rebuild_handle(name, method, stream, model_id) -> "DeploymentHandle":
    return DeploymentHandle(name, method, _stream=stream,
                            _model_id=model_id)


class _HandleMethod:
    def __init__(self, handle: DeploymentHandle, method: str):
        self._handle = handle
        self._method = method

    def remote(self, *args, **kwargs):
        return self._handle.options(self._method).remote(*args, **kwargs)


class Application:
    def __init__(self, deployment: "Deployment", args, kwargs):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


class Deployment:
    def __init__(self, cls_or_fn, name: str, num_replicas: int = 1,
                 max_ongoing_requests: int = 100,
                 ray_actor_options: Optional[Dict] = None,
                 autoscaling_config: Optional[Dict[str, Any]] = None,
                 placement_strategy: Optional[str] = None,
                 init_kwargs: Optional[Dict[str, Any]] = None):
        self._cls_or_fn = cls_or_fn
        self.name = name
        self.num_replicas = num_replicas
        self.max_ongoing_requests = max_ongoing_requests
        self.ray_actor_options = ray_actor_options or {}
        self.autoscaling_config = autoscaling_config
        self.placement_strategy = placement_strategy
        # Constructor overrides merged over bind() kwargs at deploy time:
        # config-file deploys tune replica knobs (e.g. the LLM engine's
        # num_slots / max_len / use_decode_kernel) without editing the
        # application module.
        self.init_kwargs = dict(init_kwargs or {})

    def options(self, *, num_replicas: Optional[Any] = None,
                name: Optional[str] = None,
                max_ongoing_requests: Optional[int] = None,
                autoscaling_config: Optional[Dict[str, Any]] = None,
                placement_strategy: Optional[str] = None,
                ray_actor_options: Optional[Dict] = None,
                init_kwargs: Optional[Dict[str, Any]] = None,
                **_) -> "Deployment":
        return Deployment(
            self._cls_or_fn, name or self.name,
            num_replicas or self.num_replicas,
            max_ongoing_requests or self.max_ongoing_requests,
            ray_actor_options if ray_actor_options is not None
            else self.ray_actor_options,
            autoscaling_config if autoscaling_config is not None
            else self.autoscaling_config,
            placement_strategy or self.placement_strategy,
            init_kwargs if init_kwargs is not None else self.init_kwargs)

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)


def deployment(_cls=None, *, name: Optional[str] = None,
               num_replicas: Any = 1, max_ongoing_requests: int = 100,
               autoscaling_config: Optional[Dict[str, Any]] = None,
               placement_strategy: Optional[str] = None,
               ray_actor_options: Optional[Dict] = None,
               **kwargs):
    """``@serve.deployment`` decorator (class or function).

    ``num_replicas="auto"`` or an ``autoscaling_config`` dict (min_replicas,
    max_replicas, target_ongoing_requests, upscale/downscale_delay_s)
    enables autoscaling (reference: serve autoscaling_policy.py).
    """

    def decorate(cls_or_fn):
        return Deployment(cls_or_fn, name or cls_or_fn.__name__,
                          num_replicas, max_ongoing_requests,
                          ray_actor_options=ray_actor_options,
                          autoscaling_config=autoscaling_config,
                          placement_strategy=placement_strategy)

    if _cls is not None:
        return decorate(_cls)
    return decorate


def _get_or_start_controller():
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        controller_cls = ray_tpu.remote(ServeController)
        return controller_cls.options(
            name=CONTROLLER_NAME, lifetime="detached", max_concurrency=16,
            get_if_exists=True).remote()


def _resolve_bound_args(controller, value, deployed: Dict[str, Any]):
    """Replace nested bound ``Application``s (anywhere in args, including
    inside lists/tuples/dicts) with handles to their freshly-deployed
    deployments — depth-first, so leaves deploy before their consumers
    (reference: ``build_app`` recursion, serve/_private/build_app.py:68)."""
    if isinstance(value, Application):
        return _deploy_application(controller, value, deployed)
    if isinstance(value, (list, tuple)):
        return type(value)(
            _resolve_bound_args(controller, v, deployed) for v in value)
    if isinstance(value, dict):
        return {k: _resolve_bound_args(controller, v, deployed)
                for k, v in value.items()}
    return value


def _deploy_application(controller, app: Application,
                        deployed: Dict[str, Any]) -> DeploymentHandle:
    dep = app.deployment
    if dep.name in deployed:
        # Diamond graphs: one deployment bound into several consumers
        # deploys once and shares its handle.
        return deployed[dep.name]
    import inspect

    args = tuple(_resolve_bound_args(controller, a, deployed)
                 for a in app.args)
    kwargs = {k: _resolve_bound_args(controller, v, deployed)
              for k, v in app.kwargs.items()}
    if dep.init_kwargs:
        # Config overrides win over bind(). Rebind positional bind()
        # args by name first, so overriding e.g. a positionally-bound
        # num_slots retunes it instead of crashing the replica with a
        # duplicate-argument TypeError.
        try:
            sig = inspect.signature(dep._cls_or_fn)
        except (TypeError, ValueError):   # C callables etc.
            sig = None
        var_kw = None if sig is None else next(
            (p.name for p in sig.parameters.values()
             if p.kind is inspect.Parameter.VAR_KEYWORD), None)
        if sig is not None and var_kw is None:
            unknown = set(dep.init_kwargs) - set(sig.parameters)
            if unknown:
                removed = getattr(dep._cls_or_fn, "removed_init_kwargs", {})
                raise ValueError(
                    f"init_kwargs {sorted(unknown)} not accepted by "
                    f"{dep.name}'s constructor" + "".join(
                        f"; {removed[k]}" for k in sorted(unknown)
                        if k in removed))
        try:
            bound = sig.bind_partial(*args, **kwargs)
            for key, value in dep.init_kwargs.items():
                if key in sig.parameters and key != var_kw:
                    bound.arguments[key] = value
                else:
                    # **kwargs catch-all: BoundArguments nests extras
                    # under the VAR_KEYWORD parameter; top-level keys
                    # would be silently dropped.
                    bound.arguments.setdefault(var_kw, {})[key] = value
            args, kwargs = bound.args, dict(bound.kwargs)
        except (TypeError, AttributeError):   # sig None / args mismatch
            kwargs = {**kwargs, **dep.init_kwargs}
    is_function = not inspect.isclass(dep._cls_or_fn)
    ray_tpu.get(controller.deploy.remote(
        dep.name, dep._cls_or_fn, args, kwargs, dep.num_replicas,
        is_function, dep.max_ongoing_requests, dep.autoscaling_config,
        dep.placement_strategy, dep.ray_actor_options),
        timeout=120)
    handle = DeploymentHandle(dep.name)
    deployed[dep.name] = handle
    return handle


def run(app: Application, *, name: str = "default",
        route_prefix: Optional[str] = None) -> DeploymentHandle:
    """Deploy an application GRAPH: nested bound deployments (an
    ``Application`` passed as an init arg) deploy recursively and the
    consumer receives a ``DeploymentHandle`` in their place — multi-stage
    pipelines (preprocess → LLM → postprocess) compose naturally
    (reference: ``serve.run`` + ``build_app``)."""
    controller = _get_or_start_controller()
    return _deploy_application(controller, app, {})


def get_deployment_handle(name: str, app_name: str = "default") -> DeploymentHandle:
    return DeploymentHandle(name)


def drain(name: str, count: int = 1) -> int:
    """Gracefully drain ``count`` replicas of deployment ``name`` out of
    rotation (operator surface — a rolling replace): each drained
    replica stops admitting, leaves the routing ring, finishes its
    in-flight requests up to ``RAY_TPU_SERVE_DRAIN_S``, and is replaced
    by a fresh replica. Returns how many drains started."""
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(
        controller.drain_replicas.remote(name, count, "operator"),
        timeout=30)


def delete(name: str):
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        ray_tpu.get(controller.delete.remote(name), timeout=30)
    except ValueError:
        pass


def shutdown():
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        ray_tpu.get(controller.shutdown.remote(), timeout=30)
        ray_tpu.kill(controller)
    except Exception:  # noqa: BLE001
        pass


# ------------------------------------------------------------- data plane
# The ingress implementations (asyncio HTTP + gRPC over a shared router)
# live in serve/proxy.py; these module-level helpers manage the default
# instances (reference: serve.start(http_options=...)).
_proxy = None
_grpc_proxy = None
_shared_router = None


def _router():
    global _shared_router
    if _shared_router is None:
        from ray_tpu.serve.proxy import _Router

        _shared_router = _Router()
    return _shared_router


def start_http(host: str = "127.0.0.1", port: int = 8000) -> int:
    """Start the asyncio HTTP ingress; returns the bound port."""
    global _proxy
    if _proxy is None:
        from ray_tpu.serve.proxy import AsyncHttpProxy

        _proxy = AsyncHttpProxy(host, port, router=_router())
    return _proxy.port


def stop_http():
    global _proxy
    if _proxy is not None:
        _proxy.stop()
        _proxy = None


def start_grpc(host: str = "127.0.0.1", port: int = 0) -> int:
    """Start the gRPC ingress (ServeIngress service); returns the port."""
    global _grpc_proxy
    if _grpc_proxy is None:
        from ray_tpu.serve.proxy import GrpcProxy

        _grpc_proxy = GrpcProxy(host, port, router=_router())
    return _grpc_proxy.port


def stop_grpc():
    global _grpc_proxy
    if _grpc_proxy is not None:
        _grpc_proxy.stop()
        _grpc_proxy = None
