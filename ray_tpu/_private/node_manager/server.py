"""Node manager: the per-node daemon (raylet-equivalent).

Reference: ``src/ray/raylet`` (SURVEY.md C15-C21) — one process per node
running: a worker pool (spawn/reuse/idle-kill of Python worker processes,
reference ``worker_pool.h:216``), the local+cluster scheduler with spillback
(``cluster_task_manager.cc:44`` / ``local_task_manager.cc:121``), placement
bundle 2PC reservations (``placement_group_resource_manager.h``), and the
node object store + transfer endpoint (plasma + object manager, C12/C13; the
python dict store here is the interim data plane the C++ shm store replaces).
"""

from __future__ import annotations

import argparse
import logging
import os
import pickle
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu._private import metrics_defs as mdefs
from ray_tpu._private import rpc
from ray_tpu._private.scheduler import policies
from ray_tpu.protobuf import ray_tpu_pb2 as pb

logger = logging.getLogger(__name__)

HEARTBEAT_PERIOD_S = 0.5


def _heartbeat_period_s() -> float:
    """Env-tunable (RAY_TPU_HEARTBEAT_PERIOD_S) together with the GCS
    side's RAY_TPU_HEARTBEAT_TTL_S: co-tenant-loaded test boxes widen
    both instead of flaking on missed 3s liveness windows."""
    import os

    return float(os.environ.get("RAY_TPU_HEARTBEAT_PERIOD_S",
                                HEARTBEAT_PERIOD_S))
CLUSTER_VIEW_TTL_S = 1.0
IDLE_WORKER_TTL_S = 60.0
CHUNK_SIZE = 8 * 1024 * 1024


class _Worker:
    def __init__(self, worker_id: str, proc: subprocess.Popen):
        self.worker_id = worker_id
        self.proc = proc
        self.address: Optional[str] = None
        self.fast_address: str = ""  # framed-TCP task plane (fastpath.py)
        self.ready = threading.Event()
        self.leased_for: Optional[bytes] = None  # lease id
        self.is_actor_worker = False
        self.idle_since = time.monotonic()
        self.busy_since = 0.0  # set when leased (memory-monitor kill order)


def _child_pythonpath(env: Dict[str, str],
                      include_cwd: bool = False) -> str:
    """Module search path for child processes (workers, the node agent):
    they must import ray_tpu + pickled-by-reference modules from the same
    universe as this process."""
    parts = list(sys.path) + [env.get("PYTHONPATH", "")]
    if include_cwd:
        parts.append(os.getcwd())
    return os.pathsep.join(dict.fromkeys(filter(None, parts)))


class NodeManager:
    def __init__(self, gcs_address: str, port: int = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 node_id: Optional[str] = None):
        self.node_id = node_id or uuid.uuid4().hex
        self.gcs_address = gcs_address
        self.gcs = rpc.get_stub("GcsService", gcs_address)

        resources = dict(resources or {"CPU": float(os.cpu_count() or 4)})
        self.total = resources
        self.available = dict(resources)
        self._res_lock = threading.RLock()
        # Shares the resource lock so queued lease RPCs wake on release.
        self._res_cv = threading.Condition(self._res_lock)
        self._lease_queue_slots = threading.Semaphore(
            self.LEASE_QUEUE_SLOTS)
        # Instance-level TPU slot accounting (reference: per-GPU-slot
        # resource instances, common/scheduling/resource_instance_set.h):
        # whole-chip asks get concrete chip indices for TPU_VISIBLE_CHIPS.
        self._tpu_free: List[int] = list(range(int(resources.get("TPU", 0))))
        self._tpu_held: Dict[bytes, List[int]] = {}

        # object store: native shared-memory data plane (plasma-equivalent,
        # native/shm_store.cpp) with a python-dict fallback. The dict also
        # backs values received without a local shm segment.
        self._objects: Dict[bytes, bytes] = {}
        self._obj_lock = threading.RLock()
        self._shm = None
        # Spilling (reference: LocalObjectManager, local_object_manager.h:41):
        # instead of LRU-*dropping* under memory pressure, cold objects move
        # to disk and restore on access. The C++ store therefore gets an
        # unbounded capacity; the configured budget is enforced here by
        # spilling down from the high watermark to the low one.
        self._store_capacity = int(os.environ.get(
            "RAY_TPU_OBJECT_STORE_BYTES", 4 << 30))
        self._spill_dir = os.path.join(
            tempfile.gettempdir(), f"ray_tpu_spill_{self.node_id[:12]}")
        self._spilled: Dict[str, Tuple[str, int]] = {}  # oid -> (path, size)
        self._spill_lock = threading.Lock()
        self._spill_event = threading.Event()
        # Per-node agent fields (reference C21) — initialized BEFORE the
        # gRPC server / heartbeat thread go live so early RPC ticks can't
        # hit missing attributes.
        self._agent_enabled = \
            os.environ.get("RAY_TPU_DISABLE_AGENT") != "1"
        self._agent_proc: Optional[subprocess.Popen] = None
        self._agent_port = 0
        self._agent_respawn_after = 0.0
        self._agent_started_at = 0.0
        self._agent_starting = False
        # Envs seen before the agent finished starting: bounded queue,
        # flushed on start so a fresh node's first leases still pre-warm.
        self._pending_prewarm: List[bytes] = []
        try:
            from ray_tpu._private.shm import ShmStore

            self._shm = ShmStore(capacity_bytes=1 << 62)
        except Exception as e:  # noqa: BLE001
            logger.warning("native shm store unavailable (%s); "
                           "using in-memory store", e)

        # worker pool
        self._workers: Dict[str, _Worker] = {}
        self._idle: List[str] = []
        self._pool_lock = threading.RLock()
        self._spawning_task = 0   # in-flight spawns counted against the caps
        self._spawning_actor = 0

        # placement bundles (reference: placement_group_resource_manager.h).
        # Prepare holds the group's node-total demand; commit converts it to
        # per-bundle availability that PG-targeted leases charge against.
        self._prepared: Dict[bytes, Dict[str, float]] = {}
        self._pg_avail: Dict[bytes, Dict[int, Dict[str, float]]] = {}
        self._pg_totals: Dict[bytes, Dict[int, Dict[str, float]]] = {}
        # holder (lease or actor id) -> (group_id, bundle_index) it charged
        self._pg_holders: Dict[bytes, Tuple[bytes, int]] = {}
        # outstanding leases / actor resource holds
        self._leases: Dict[bytes, Tuple[str, Dict[str, float]]] = {}
        self._actor_demands: Dict[bytes, Tuple[str, Dict[str, float]]] = {}

        # cluster view: seeded/backstopped by a GetNodes poll, kept fresh
        # by NODE_RES availability deltas + NODE liveness events pushed
        # over pubsub (reference C9 ray_syncer gossip — push, not poll).
        self._view: List[pb.NodeInfo] = []
        self._view_ts = 0.0
        self._view_lock = threading.Lock()
        self._view_subscribed = False

        # Sender-side transfer caps (reference C13 PushManager,
        # push_manager.h:30): bound concurrent outbound object streams so
        # a hot object can't monopolize every handler thread + the NIC.
        self._push_slots = threading.BoundedSemaphore(
            int(os.environ.get("RAY_TPU_MAX_CONCURRENT_PUSHES", 8)))

        self._stop = threading.Event()
        # Observability: per-node tag for every series this daemon emits;
        # the per-process pusher ships them to the head TSDB (a no-op when
        # the GCS runs in this process — it samples the registry itself).
        # Set before the gRPC server goes live: lease RPCs touch both.
        self._mtags = {"node_id": self.node_id[:12]}
        self._queued_leases = 0
        self._queued_leases_lock = threading.Lock()
        # Pool sized above any single driver's submit concurrency: queued
        # lease RPCs briefly hold server threads (see _queue_for_resources).
        self._server, self.port = rpc.serve("NodeService", self, port=port,
                                            max_workers=128)
        self.address = f"127.0.0.1:{self.port}"
        # Binary object plane: owners flush put metadata / batches over
        # framed TCP instead of per-batch gRPC (the gRPC stack's CPU was
        # visible in the large-put path on small hosts).
        from ray_tpu._private import fastpath as _fastpath

        self._fast = _fastpath.FastServer(self._fast_handler)
        self.fast_address = self._fast.address

        info = pb.NodeInfo(node_id=self.node_id, address=self.address,
                           alive=True, fast_address=self.fast_address)
        for k, v in self.total.items():
            info.resources[k] = v
            info.available[k] = v
        for k, v in (labels or {}).items():
            info.labels[k] = v
        self.labels = dict(labels or {})
        # The very first RPC to a GCS that may have started milliseconds
        # ago: retry briefly on connection refusal (its gRPC listener can
        # lag the constructor's return under load) instead of failing a
        # node bootstrap on a startup race.
        deadline = time.monotonic() + 5.0
        while True:
            try:
                self.gcs.RegisterNode(pb.RegisterNodeRequest(info=info))
                break
            except Exception:  # noqa: BLE001 — UNAVAILABLE during startup
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.1)
        from ray_tpu._private import metrics_pusher, xla_monitor

        metrics_pusher.ensure_pusher(gcs_address,
                                     labels={"role": "node_manager"})
        xla_monitor.connect(gcs_address, node_id=self.node_id)
        threading.Thread(target=self._metrics_loop, daemon=True,
                         name="nm-metrics").start()

        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True, name="nm-heartbeat")
        self._hb_thread.start()
        threading.Thread(target=self._view_subscriber_loop, daemon=True,
                         name="nm-view-sub").start()
        # Prestart workers so first leases don't pay process-spawn latency
        # (reference: worker pool prestart, worker_pool.h:216).
        threading.Thread(target=self._prestart_workers, daemon=True).start()
        # Memory monitor (reference: memory_monitor.h:52): sheds the newest
        # leased task worker under host memory pressure so the OS OOM killer
        # never picks a victim at random. Kill cause surfaces through the
        # normal worker-crash retry path.
        self._mem_threshold = float(os.environ.get(
            "RAY_TPU_MEMORY_USAGE_THRESHOLD", 0.95))
        self._mem_usage_file = os.environ.get("RAY_TPU_MEMORY_USAGE_FILE", "")
        self.oom_kills = 0
        threading.Thread(target=self._memory_monitor_loop, daemon=True,
                         name="nm-memmon").start()
        if self._shm is not None:
            threading.Thread(target=self._spill_loop, daemon=True,
                             name="nm-spill").start()
        # Per-node agent (reference C21, raylet/agent_manager.h): spawned
        # as a subprocess, supervised (respawned) from the heartbeat loop,
        # does runtime-env pre-warm + node stats. Disabled via env for
        # tests that count processes.
        if self._agent_enabled:
            self._launch_agent()

    def _prestart_workers(self):
        n = min(int(self.total.get("CPU", 1)), 4)
        workers = []
        for _ in range(n):
            if self._stop.is_set():
                return
            workers.append(self._spawn_worker())
        for w in workers:
            if w.ready.wait(30) and not self._stop.is_set():
                with self._pool_lock:
                    if w.worker_id not in self._idle and w.leased_for is None:
                        self._idle.append(w.worker_id)

    # ------------------------------------------------------------ resources
    def _try_acquire(self, demand: Dict[str, float],
                     holder: Optional[bytes] = None) -> bool:
        with self._res_lock:
            if all(self.available.get(k, 0.0) + 1e-9 >= v
                   for k, v in demand.items()):
                for k, v in demand.items():
                    self.available[k] = self.available.get(k, 0.0) - v
                n_chips = int(demand.get("TPU", 0))
                if holder is not None and n_chips >= 1 and \
                        n_chips == demand.get("TPU"):
                    self._tpu_held[holder] = \
                        [self._tpu_free.pop() for _ in range(n_chips)]
                return True
            return False

    def _chips_for(self, holder: bytes) -> List[int]:
        with self._res_lock:
            return list(self._tpu_held.get(holder, []))

    def _release(self, demand: Dict[str, float],
                 holder: Optional[bytes] = None):
        with self._res_cv:
            for k, v in demand.items():
                self.available[k] = min(
                    self.available.get(k, 0.0) + v, self.total.get(k, 0.0))
            if holder is not None:
                self._tpu_free.extend(self._tpu_held.pop(holder, []))
            self._res_cv.notify_all()  # wake queued lease requests

    def _acquire_from_bundle(self, group_id: bytes, bundle_index: int,
                             demand: Dict[str, float],
                             holder: bytes) -> Tuple[bool, str]:
        """Charge ``demand`` against a committed bundle's reservation instead
        of free node capacity (reference:
        ``placement_group_resource_manager.h`` — bundles own CPU_group_...
        resource instances; here they own per-bundle availability maps).

        Chip slots were debited from ``available`` at prepare time but left
        in ``_tpu_free``; a PG lease claims its physical slots here.
        """
        with self._res_lock:
            bundles = self._pg_avail.get(group_id)
            if bundles is None:
                return False, "pg-unknown"
            indices = [bundle_index] if bundle_index >= 0 else sorted(bundles)
            for i in indices:
                avail = bundles.get(i)
                if avail is None:
                    continue
                if all(avail.get(k, 0.0) + 1e-9 >= v
                       for k, v in demand.items()):
                    for k, v in demand.items():
                        avail[k] = avail.get(k, 0.0) - v
                    n_chips = int(demand.get("TPU", 0))
                    if n_chips >= 1 and n_chips == demand.get("TPU"):
                        self._tpu_held[holder] = \
                            [self._tpu_free.pop() for _ in range(n_chips)]
                    self._pg_holders[holder] = (group_id, i)
                    return True, ""
            totals = self._pg_totals.get(group_id, {})
            fits_ever = any(
                all(t.get(k, 0.0) + 1e-9 >= v for k, v in demand.items())
                for i, t in totals.items()
                if bundle_index < 0 or i == bundle_index)
            return False, ("pg-wait" if fits_ever else "infeasible")

    def _release_pg_holder(self, holder: bytes,
                           demand: Dict[str, float]) -> bool:
        """Return a PG lease/actor charge to its bundle. False if ``holder``
        never charged a bundle (caller falls back to node release). If the
        group was removed while the holder ran, its share was the only part
        of the reservation not yet returned to the node — credit it now."""
        with self._res_lock:
            key = self._pg_holders.pop(holder, None)
            if key is None:
                return False
            self._tpu_free.extend(self._tpu_held.pop(holder, []))
            group_id, idx = key
            bundles = self._pg_avail.get(group_id)
            if bundles is None or idx not in bundles:
                for k, v in demand.items():
                    self.available[k] = min(
                        self.available.get(k, 0.0) + v, self.total.get(k, 0.0))
                return True
            avail = bundles[idx]
            for k, v in demand.items():
                avail[k] = avail.get(k, 0.0) + v
            return True

    def _fast_handler(self, kind: int, payload: bytes) -> bytes:
        """Binary object plane (fastpath.py): put-batch flushes (sync
        large-put registration + the flusher's batches) skip the gRPC
        stack — measurable CPU per call on small hosts."""
        from ray_tpu._private import fastpath

        if kind == fastpath.KIND_PUT_BATCH:
            req = pb.PutObjectBatchRequest()
            req.ParseFromString(payload)
            return self.PutObjectBatch(req, None).SerializeToString()
        raise ValueError(f"unknown fastpath frame kind {kind}")

    def _heartbeat_loop(self):
        from ray_tpu._private import chaos

        seq = 0
        while not self._stop.wait(_heartbeat_period_s()):
            seq += 1
            # Chaos site: ``drop_node_hb`` skips this tick's GCS send —
            # the local bookkeeping below still runs, so the injected
            # fault is exactly a lost heartbeat, driving GCS liveness
            # reaping without wedging the node.
            directive = chaos.inject("node_heartbeat",
                                     node=self.node_id) or {}
            if not directive.get("drop"):
                req = pb.HeartbeatRequest(node_id=self.node_id, seq=seq)
                with self._res_lock:
                    for k, v in self.available.items():
                        req.available[k] = v
                try:
                    reply = self.gcs.Heartbeat(req, timeout=2)
                    if not reply.ok:
                        # GCS restarted / lost us: re-register.
                        info = pb.NodeInfo(node_id=self.node_id,
                                           address=self.address,
                                           alive=True,
                                           fast_address=self.fast_address)
                        for k, v in self.total.items():
                            info.resources[k] = v
                        with self._res_lock:
                            for k, v in self.available.items():
                                info.available[k] = v
                        for k, v in self.labels.items():
                            info.labels[k] = v
                        self.gcs.RegisterNode(
                            pb.RegisterNodeRequest(info=info))
                except Exception:  # noqa: BLE001
                    pass
            self._reap_idle_workers()
            self._check_dead_workers()
            self._check_agent()

    def _metrics_loop(self):
        """Dedicated sampling thread: gauge refreshes must never ride the
        heartbeat loop — under GIL saturation (worker spawn storms, task
        fan-outs) the extra per-tick python work delayed heartbeat sends
        past the 3s liveness threshold and got healthy nodes marked dead."""
        from ray_tpu._private import metrics_pusher

        interval = max(metrics_pusher.push_interval_s(), 1.0)
        while not self._stop.wait(interval):
            self._sample_node_metrics()

    def _sample_node_metrics(self):
        """Refresh this node's gauges each heartbeat tick (worker-pool
        states, lease-queue depth, store fill, host vitals)."""
        try:
            with self._pool_lock:
                total = len(self._workers)
                idle = len(self._idle)
                busy = sum(1 for w in self._workers.values()
                           if w.leased_for is not None)
            for state, count in (("total", total), ("idle", idle),
                                 ("busy", busy)):
                mdefs.NODE_WORKERS.set(count, tags={**self._mtags,
                                                    "state": state})
            mdefs.NODE_LEASE_QUEUE.set(self._queued_leases,
                                       tags=self._mtags)
            if self._shm is not None:
                used, count = self._shm.stats()
                mdefs.STORE_USED_BYTES.set(used, tags=self._mtags)
                mdefs.STORE_OBJECTS.set(count, tags=self._mtags)
            # Host vitals (mem/load/disk) are published by the node
            # AGENT's vitals loop only — a second publisher here would
            # double-count the host under agg=sum queries.
        except Exception:  # noqa: BLE001 — sampling must never kill the
            pass           # heartbeat loop

    # ------------------------------------------------------------- agent
    AGENT_START_GRACE_S = 60.0

    def _launch_agent(self) -> None:
        """Start _start_agent at most once at a time: without the flag a
        slow Popen lets the supervisor double-spawn and leak the loser."""
        if self._agent_starting or self._stop.is_set():
            return
        self._agent_starting = True
        threading.Thread(target=self._start_agent, daemon=True,
                         name="nm-agent-start").start()

    def _start_agent(self) -> None:
        """Spawn the per-node agent subprocess and read its port."""
        try:
            self._start_agent_inner()
        finally:
            self._agent_starting = False

    def _start_agent_inner(self) -> None:
        import sys

        if self._stop.is_set():
            return
        self._agent_started_at = time.monotonic()
        env = dict(os.environ)
        # The agent must import ray_tpu from wherever this process got it
        # (same rule as worker spawns).
        env["PYTHONPATH"] = _child_pythonpath(env)
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.agent",
                 "--gcs-address", self.gcs_address,
                 "--node-id", self.node_id,
                 "--spill-dir", self._spill_dir],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env)
        except Exception:  # noqa: BLE001
            # _check_agent retries after the respawn window (a one-off
            # fork failure must not kill supervision for good).
            logger.exception("node agent spawn failed")
            self._agent_respawn_after = time.monotonic() + 5.0
            return
        self._agent_proc = proc
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not self._stop.is_set():
            line = proc.stdout.readline().strip()
            if line.startswith("AGENT_PORT="):
                self._agent_port = int(line.split("=", 1)[1])
                if self._stop.is_set():
                    break
                pending, self._pending_prewarm = \
                    self._pending_prewarm[-16:], []
                for blob in pending:
                    self._prewarm_runtime_env(blob)
                return
            if not line and proc.poll() is not None:
                return
        # Stopped (or timed out) mid-start: don't orphan the subprocess.
        if self._stop.is_set():
            try:
                proc.terminate()
            except Exception:  # noqa: BLE001
                pass

    def _check_agent(self) -> None:
        """Respawn a dead/hung/never-started agent (reference AgentManager
        supervision), rate-limited so a crash loop doesn't spin."""
        if not self._agent_enabled or self._stop.is_set() \
                or self._agent_starting:
            return
        now = time.monotonic()
        proc = self._agent_proc
        if proc is not None and proc.poll() is None:
            if self._agent_port:
                return
            # Alive but never reported a port: give it the start grace,
            # then treat as hung and recycle.
            if now - self._agent_started_at < self.AGENT_START_GRACE_S:
                return
            try:
                proc.terminate()
            except Exception:  # noqa: BLE001
                pass
        if now < self._agent_respawn_after:
            return
        self._agent_respawn_after = now + 5.0
        self._agent_proc = None
        self._agent_port = 0
        if proc is not None:
            logger.warning("node agent died/hung (rc=%s); respawning",
                           proc.returncode)
        self._launch_agent()

    def _prewarm_runtime_env(self, runtime_env_blob: bytes) -> None:
        """Forward a lease's runtime env to the agent so the venv build /
        package download overlaps with placement (fire-and-forget)."""
        if not runtime_env_blob or not self._agent_enabled:
            return
        try:
            renv = pickle.loads(bytes(runtime_env_blob))
        except Exception:  # noqa: BLE001
            return
        # Ask the plugin registry which fields need building rather than
        # hardcoding them — a new plugin (conda's long builds most of all)
        # must be prewarmable without touching this gate.
        from ray_tpu._private.runtime_env import plugin as plugin_mod

        if not any(p.prewarmable and renv.get(p.name)
                   for p in plugin_mod.plugins_for(renv)):
            return  # env_vars-only: nothing to build, no thread to spawn
        if not self._agent_port:
            if len(self._pending_prewarm) < 16:
                self._pending_prewarm.append(bytes(runtime_env_blob))
            return

        def post():
            try:
                import json as _json
                import urllib.request

                req = urllib.request.Request(
                    f"http://127.0.0.1:{self._agent_port}"
                    "/runtime_env/prewarm",
                    data=_json.dumps(renv).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:  # noqa: BLE001 — pre-warm is best-effort
                pass

        threading.Thread(target=post, daemon=True).start()

    def _view_subscriber_loop(self):
        """Consume NODE_RES availability deltas + NODE liveness events
        (reference C9: ray_syncer's push-based resource view). While the
        stream is live the GetNodes poll drops to a slow backstop."""
        while not self._stop.is_set():
            try:
                stream = self.gcs.Subscribe(pb.SubscribeRequest(
                    channels=["NODE_RES", "NODE"],
                    subscriber_id=f"nm-{self.node_id[:12]}"),
                    timeout=3600.0)
                self._view_subscribed = True
                for msg in stream:
                    if self._stop.is_set():
                        return
                    try:
                        ev = pickle.loads(msg.data)
                    except Exception:  # noqa: BLE001
                        continue
                    if msg.channel == "NODE_RES":
                        # Copy-on-write: snapshots handed out by
                        # _cluster_view share these messages, so patch a
                        # fresh copy instead of mutating one a scheduler
                        # thread may be iterating.
                        with self._view_lock:
                            for i, n in enumerate(self._view):
                                if n.node_id == ev["node_id"]:
                                    cp = pb.NodeInfo()
                                    cp.CopyFrom(n)
                                    for k, v in ev["available"].items():
                                        cp.available[k] = v
                                    self._view[i] = cp
                                    break
                    else:  # NODE liveness change: force a full refresh
                        self._view_ts = 0.0
            except Exception:  # noqa: BLE001
                pass
            finally:
                self._view_subscribed = False
            if self._stop.wait(1.0):
                return

    def _cluster_view(self) -> List[pb.NodeInfo]:
        now = time.monotonic()
        ttl = (10 * CLUSTER_VIEW_TTL_S if self._view_subscribed
               else CLUSTER_VIEW_TTL_S)
        if now - self._view_ts > ttl:
            try:
                fresh = list(
                    self.gcs.GetNodes(pb.GetNodesRequest(), timeout=2).nodes)
                with self._view_lock:
                    self._view = fresh
                    self._view_ts = now
            except Exception:  # noqa: BLE001
                pass
        with self._view_lock:
            return list(self._view)

    # ------------------------------------------------------------ worker pool
    def _spawn_worker(self) -> _Worker:
        worker_id = uuid.uuid4().hex
        cmd = [
            sys.executable, "-m", "ray_tpu._private.workers.default_worker",
            "--node-address", self.address,
            "--gcs-address", self.gcs_address,
            "--worker-id", worker_id,
            "--node-id", self.node_id,
        ]
        env = dict(os.environ)
        # Workers must resolve pickled-by-reference functions from the same
        # module universe as the submitting process (includes pytest's
        # sys.path injections when the node manager runs in a test process).
        env["PYTHONPATH"] = _child_pythonpath(env, include_cwd=True)
        proc = subprocess.Popen(cmd, env=env)
        worker = _Worker(worker_id, proc)
        with self._pool_lock:
            self._workers[worker_id] = worker
        return worker

    def _pop_worker(self, timeout_s: float = 30.0,
                    for_actor: bool = False) -> Optional[_Worker]:
        """Reference: WorkerPool::PopWorker (worker_pool.cc:1355).

        Task-worker spawn is capped (reference: maximum_startup_concurrency):
        a burst of zero-CPU leases must not fork-bomb the host — beyond the
        cap the lease waits briefly for a worker to free and otherwise
        retries from the client with backoff. Dedicated actor workers count
        against a separate, much larger cap (actors legitimately number in
        the dozens; their admission is governed by resources, not the pool).
        """
        if for_actor:
            cap = int(os.environ.get("RAY_TPU_MAX_ACTOR_WORKERS", 128))
        else:
            # 2x CPU: the headroom matters for nested tasks — parents
            # blocked in ray.get occupy workers, and a 1x cap would
            # livelock a full-width nested fan-out (workers are not
            # released while blocked).
            cap = int(os.environ.get(
                "RAY_TPU_MAX_WORKERS",
                max(4, int(self.total.get("CPU", 4)) * 2)))
        start = time.monotonic()
        reserved = False
        while True:
            with self._pool_lock:
                while self._idle:
                    wid = self._idle.pop()
                    w = self._workers.get(wid)
                    if w and w.proc.poll() is None:
                        return w
                if for_actor:
                    used = sum(1 for w in self._workers.values()
                               if w.is_actor_worker)
                    used += self._spawning_actor
                else:
                    used = sum(1 for w in self._workers.values()
                               if not w.is_actor_worker)
                    used += self._spawning_task
                if used < cap:
                    # Reserve the slot under the lock — concurrent lease
                    # RPCs must not all pass the check before any spawn
                    # registers (that is the fork-bomb the cap prevents).
                    if for_actor:
                        self._spawning_actor += 1
                    else:
                        self._spawning_task += 1
                    reserved = True
            if reserved:
                break
            if time.monotonic() - start > 1.0:  # wait ≤1s at the cap
                return None
            time.sleep(0.005)
        try:
            worker = self._spawn_worker()
            if for_actor:
                worker.is_actor_worker = True
        finally:
            with self._pool_lock:
                if for_actor:
                    self._spawning_actor -= 1
                else:
                    self._spawning_task -= 1
        if worker.ready.wait(timeout_s):
            return worker
        return None

    def _reap_idle_workers(self):
        now = time.monotonic()
        reaped = []
        with self._pool_lock:
            keep = []
            for wid in self._idle:
                w = self._workers.get(wid)
                if w is None or w.proc.poll() is not None:
                    continue
                if now - w.idle_since > IDLE_WORKER_TTL_S:
                    w.proc.terminate()
                    self._workers.pop(wid, None)
                    reaped.append(wid)
                else:
                    keep.append(wid)
            self._idle = keep
        for wid in reaped:
            try:
                self.gcs.ReapHolder(
                    pb.ReapHolderRequest(holder_id=wid), timeout=5)
            except Exception:  # noqa: BLE001
                pass

    def _check_dead_workers(self):
        """Detect crashed actor workers and hand the restart decision to the
        GCS (reference: raylet worker-death notification →
        GcsActorManager::OnWorkerDead)."""
        with self._pool_lock:
            dead = [w for w in self._workers.values()
                    if w.proc.poll() is not None]
            for w in dead:
                self._workers.pop(w.worker_id, None)
                if w.worker_id in self._idle:
                    self._idle.remove(w.worker_id)
        for w in dead:
            # A dead worker's refcounts would pin objects forever: reap its
            # holder at the GCS (reference: refs tied to owner liveness).
            try:
                self.gcs.ReapHolder(
                    pb.ReapHolderRequest(holder_id=w.worker_id), timeout=5)
            except Exception:  # noqa: BLE001
                pass
            for actor_id, (wid, demand) in list(self._actor_demands.items()):
                if wid != w.worker_id:
                    continue
                del self._actor_demands[actor_id]
                if not self._release_pg_holder(actor_id, demand):
                    self._release(demand, holder=actor_id)
                try:
                    reply = self.gcs.GetActor(
                        pb.GetActorRequest(actor_id=actor_id), timeout=5)
                    if reply.found and reply.info.state == "ALIVE" \
                            and reply.info.node_id == self.node_id:
                        info = reply.info
                        info.state = "RESTARTING"
                        info.death_cause = "worker process died"
                        self.gcs.UpdateActor(
                            pb.UpdateActorRequest(info=info), timeout=5)
                except Exception:  # noqa: BLE001
                    pass

    def AnnounceWorker(self, request, context):
        with self._pool_lock:
            w = self._workers.get(request.worker_id)
            if w is None:
                # Unknown worker (e.g. an orphan from a dead node manager that
                # hit a reused port): reject — it will exit on its own.
                logger.warning("rejecting unknown worker %s",
                               request.worker_id[:8])
                return pb.Empty()
            w.address = request.address
            w.fast_address = request.fast_address
            w.ready.set()
        return pb.Empty()

    # ------------------------------------------------------------ leases
    def RequestWorkerLease(self, request, context):
        """Reference: NodeManager::HandleRequestWorkerLease
        (raylet/node_manager.cc:1868) + ClusterTaskManager scheduling."""
        spec = request.spec
        demand = dict(spec.resources)
        lease_id = uuid.uuid4().bytes
        if spec.runtime_env:
            self._prewarm_runtime_env(spec.runtime_env)
        if spec.placement_group_id:
            # PG-targeted: charge the bundle reservation; never spill back —
            # the bundle lives here or nowhere (bundle_scheduling_policy.h).
            ok, err = self._acquire_from_bundle(
                bytes(spec.placement_group_id), spec.pg_bundle_index,
                demand, lease_id)
            if not ok:
                return pb.LeaseReply(granted=False, error=err)
            worker = self._pop_worker()
            if worker is None:
                self._release_pg_holder(lease_id, demand)
                return pb.LeaseReply(granted=False,
                                     error="worker start timeout")
            worker.leased_for = lease_id
            worker.busy_since = time.monotonic()
            with self._pool_lock:
                if worker.worker_id in self._idle:
                    self._idle.remove(worker.worker_id)
            self._leases[lease_id] = (worker.worker_id, demand)
            return pb.LeaseReply(granted=True,
                                 worker_address=worker.address,
                                 worker_fast_address=worker.fast_address,
                                 worker_id=worker.worker_id,
                                 tpu_chips=self._chips_for(lease_id))
        selector = policies.parse_label_selector(spec.label_selector)
        if selector is not None:
            return self._lease_with_labels(spec, demand, lease_id, selector)
        if spec.strategy == "SPREAD":
            # Min-utilization placement (reference: spread_scheduling_policy):
            # compare POST-charge utilization — what each node would look
            # like with this task on it — or an idle-but-small local node
            # swallows a whole fan-out serially. A small margin damps
            # spillback ping-pong between nodes with stale views.
            others = [n for n in self._cluster_view()
                      if n.node_id != self.node_id]
            best = policies.pick_node_spread(others, demand)
            if best is not None:
                me = pb.NodeInfo(node_id=self.node_id, alive=True)
                with self._res_lock:
                    for k, v in self.total.items():
                        me.resources[k] = v
                    for k, v in self.available.items():
                        me.available[k] = v
                best_node = next(n for n in others if n.node_id == best)
                if policies.util_after(best_node, demand) + 0.02 < \
                        policies.util_after(me, demand):
                    return pb.LeaseReply(granted=False,
                                         spillback_node_id=best,
                                         spillback_address=best_node.address)
        if self._try_acquire(demand, holder=lease_id):
            return self._grant_lease(lease_id, demand)
        if spec.affinity_node_id and not spec.affinity_soft:
            # Hard node affinity (NodeAffinitySchedulingStrategy): never
            # spill; the task waits for local resources, or fails if this
            # node can never hold the demand.
            if not all(self.total.get(k, 0.0) + 1e-9 >= v
                       for k, v in demand.items()):
                return pb.LeaseReply(granted=False, error="infeasible")
            return self._queue_for_resources(lease_id, demand)
        # Spillback: pick another node from the cluster view.
        nodes = [n for n in self._cluster_view() if n.node_id != self.node_id]
        picker = (policies.pick_node_spread if spec.strategy == "SPREAD"
                  else policies.pick_node_hybrid)
        target = picker(nodes, demand)
        if target is None:
            if not policies.feasible_anywhere(self._cluster_view(), demand):
                return pb.LeaseReply(granted=False, error="infeasible")
            # Nowhere else to go right now: queue locally instead of making
            # the client poll-with-backoff (the idle gaps between client
            # retries were the dominant cost of task fan-out).
            return self._queue_for_resources(lease_id, demand)
        addr = next(n.address for n in nodes if n.node_id == target)
        return pb.LeaseReply(granted=False, spillback_node_id=target,
                             spillback_address=addr)

    def _lease_with_labels(self, spec, demand: Dict[str, float],
                           lease_id: bytes, selector: Dict[str, dict]):
        """Node-label scheduling (reference: node-label scheduling policy):
        hard selectors gate eligibility, soft selectors rank, then the base
        policy places among the surviving tier. The TPU-native use is
        targeting one ICI slice (``hard={"tpu-slice": ...}``)."""
        hard = selector.get("hard") or {}
        soft = selector.get("soft") or {}
        local_hard = policies.match_labels(self.labels, hard)
        local_soft = local_hard and policies.match_labels(self.labels, soft)
        view = self._cluster_view()
        others = [n for n in view if n.node_id != self.node_id]
        picker = (policies.pick_node_spread if spec.strategy == "SPREAD"
                  else policies.pick_node_hybrid)
        if soft:
            if local_soft and self._try_acquire(demand, holder=lease_id):
                return self._grant_lease(lease_id, demand)
            # Prefer a soft-matching node with capacity right now; when the
            # soft tier has no capacity anywhere, fall through to the hard
            # tier instead of spilling forever (soft is a preference, not a
            # requirement — a soft-only selector must not livelock).
            soft_fit = [n for n in others if n.alive
                        and policies.match_labels(dict(n.labels), hard)
                        and policies.match_labels(dict(n.labels), soft)]
            target = picker(soft_fit, demand)
            if target is not None:
                addr = next(n.address for n in others
                            if n.node_id == target)
                return pb.LeaseReply(granted=False,
                                     spillback_node_id=target,
                                     spillback_address=addr)
        if local_hard and self._try_acquire(demand, holder=lease_id):
            return self._grant_lease(lease_id, demand)
        hard_fit = [n for n in others if n.alive
                    and policies.match_labels(dict(n.labels), hard)]
        target = picker(hard_fit, demand)
        if target is not None:
            addr = next(n.address for n in others if n.node_id == target)
            return pb.LeaseReply(granted=False, spillback_node_id=target,
                                 spillback_address=addr)
        if not policies.feasible_with_labels(view, demand, selector):
            return pb.LeaseReply(granted=False, error="infeasible")
        if local_hard:
            return self._queue_for_resources(lease_id, demand)
        # Eligible nodes exist but are momentarily full: client backs off.
        return pb.LeaseReply(granted=False)

    def _grant_lease(self, lease_id: bytes, demand: Dict[str, float]):
        worker = self._pop_worker()
        if worker is None:
            self._release(demand, holder=lease_id)
            return pb.LeaseReply(granted=False,
                                 error="worker start timeout")
        worker.leased_for = lease_id
        worker.busy_since = time.monotonic()
        with self._pool_lock:
            if worker.worker_id in self._idle:
                self._idle.remove(worker.worker_id)
        # Stash demand so ReturnWorker releases it.
        self._leases[lease_id] = (worker.worker_id, demand)
        mdefs.NODE_LEASES_GRANTED.inc(tags=self._mtags)
        return pb.LeaseReply(granted=True,
                             worker_address=worker.address,
                             worker_fast_address=worker.fast_address,
                             worker_id=worker.worker_id,
                             tpu_chips=self._chips_for(lease_id))

    LEASE_QUEUE_WAIT_S = 2.0
    # Cap on concurrently-queued lease RPCs: each holds a server thread,
    # and filling the whole pool with them would starve ReturnWorker — the
    # very RPC that frees the resources they wait for.
    LEASE_QUEUE_SLOTS = 32

    def _queue_for_resources(self, lease_id: bytes,
                             demand: Dict[str, float]):
        """Hold the lease RPC briefly until resources free up (reference:
        the raylet queues lease requests; clients never poll). Bounded in
        duration AND in concurrency — on either limit the client's retry
        loop takes over."""
        if not self._lease_queue_slots.acquire(blocking=False):
            return pb.LeaseReply(granted=False)
        with self._queued_leases_lock:
            self._queued_leases += 1
        try:
            deadline = time.monotonic() + self.LEASE_QUEUE_WAIT_S
            with self._res_cv:
                while not self._stop.is_set() and \
                        time.monotonic() < deadline:
                    if self._try_acquire(demand, holder=lease_id):
                        break
                    self._res_cv.wait(0.05)
                else:
                    return pb.LeaseReply(granted=False)
            return self._grant_lease(lease_id, demand)
        finally:
            with self._queued_leases_lock:
                self._queued_leases -= 1
            self._lease_queue_slots.release()

    def ReturnWorker(self, request, context):
        lease_id = request.lease_id
        lease = self._leases.pop(lease_id, None)
        if lease is None:
            # Fall back to any lease held by that worker.
            for lid, (wid, demand) in list(self._leases.items()):
                if wid == request.worker_id:
                    lease = self._leases.pop(lid)
                    lease_id = lid
                    break
        if lease is not None:
            _, demand = lease
            # Release exactly this lease's resources and chip slots. (Chips
            # held by live actors are keyed by actor_id and must NOT be
            # reclaimed here — see resource_instance_set.h semantics.)
            if not self._release_pg_holder(lease_id, demand):
                self._release(demand, holder=lease_id)
        with self._pool_lock:
            w = self._workers.get(request.worker_id)
            if w and w.proc.poll() is None and not w.is_actor_worker:
                w.leased_for = None
                w.idle_since = time.monotonic()
                if request.worker_id not in self._idle:
                    self._idle.append(request.worker_id)
        return pb.Empty()

    def CreateActorOnNode(self, request, context):
        """Lease a dedicated worker and instantiate the actor on it
        (reference: GcsActorScheduler raylet leg, gcs_actor_scheduler.cc:107)."""
        info = request.info
        spec = pickle.loads(info.spec)
        demand = dict(spec.get("resources", {}))
        pg = spec.get("pg")
        if pg is not None:
            ok, err = self._acquire_from_bundle(
                pg[0], pg[1], demand, bytes(info.actor_id))
            if not ok:
                return pb.CreateActorOnNodeReply(
                    ok=False, error=f"insufficient resources ({err})")
        elif not self._try_acquire(demand, holder=bytes(info.actor_id)):
            return pb.CreateActorOnNodeReply(
                ok=False, error="insufficient resources")
        worker = self._pop_worker(for_actor=True)
        if worker is None:
            if not self._release_pg_holder(bytes(info.actor_id), demand):
                self._release(demand, holder=bytes(info.actor_id))
            return pb.CreateActorOnNodeReply(ok=False,
                                             error="worker start timeout")
        worker.is_actor_worker = True
        with self._pool_lock:
            if worker.worker_id in self._idle:
                self._idle.remove(worker.worker_id)
        self._actor_demands[info.actor_id] = (worker.worker_id, demand)
        stub = rpc.get_stub("WorkerService", worker.address)
        info.node_id = self.node_id
        info.address = worker.address
        info.fast_address = worker.fast_address
        env = {}
        chips = self._chips_for(bytes(info.actor_id))
        if chips:
            env["TPU_VISIBLE_CHIPS"] = ",".join(map(str, chips))
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"1,1,{len(chips)}"
        for k, v in spec.get("runtime_env", {}).get("env_vars", {}).items():
            env[k] = str(v)
        try:
            reply = stub.CreateActor(pb.CreateActorRequest(info=info, env=env),
                                     timeout=60)
        except Exception as e:  # noqa: BLE001
            self._actor_demands.pop(info.actor_id, None)
            if not self._release_pg_holder(bytes(info.actor_id), demand):
                self._release(demand, holder=bytes(info.actor_id))
            return pb.CreateActorOnNodeReply(ok=False, error=str(e))
        if not reply.ok:
            self._actor_demands.pop(info.actor_id, None)
            if not self._release_pg_holder(bytes(info.actor_id), demand):
                self._release(demand, holder=bytes(info.actor_id))
            return pb.CreateActorOnNodeReply(ok=False, error=reply.error)
        return pb.CreateActorOnNodeReply(ok=True,
                                         worker_address=worker.address,
                                         fast_address=worker.fast_address)

    # ------------------------------------------------------------ bundles
    def PrepareBundle(self, request, context):
        total_demand: Dict[str, float] = defaultdict(float)
        for b in request.bundles:
            for k, v in b.resources.items():
                total_demand[k] += v
        # A re-prepare for the same group supersedes the previous attempt;
        # release the stale reservation or it leaks (each prepare debits).
        stale = self._prepared.pop(request.group_id, None)
        if stale is not None:
            self._release(stale)
        if self._try_acquire(dict(total_demand)):
            self._prepared[request.group_id] = dict(total_demand)
            return pb.PrepareBundleReply(success=True)
        return pb.PrepareBundleReply(success=False)

    def CommitBundle(self, request, context):
        demand = self._prepared.pop(request.group_id, None)
        if demand is None:
            return pb.Empty()  # already cancelled or duplicate commit
        with self._res_lock:
            avail = self._pg_avail.setdefault(request.group_id, {})
            totals = self._pg_totals.setdefault(request.group_id, {})
            for b in request.bundles:
                avail[b.index] = dict(b.resources)
                totals[b.index] = dict(b.resources)
        return pb.Empty()

    def CancelBundle(self, request, context):
        demand = self._prepared.pop(request.group_id, None)
        if demand is not None:
            self._release(demand)
            return pb.Empty()
        with self._res_lock:
            avail = self._pg_avail.pop(request.group_id, None)
            self._pg_totals.pop(request.group_id, None)
        if avail is not None:
            # Return only the unconsumed share; outstanding PG leases return
            # their charges straight to the node when they finish
            # (_release_pg_holder group-gone branch).
            freed: Dict[str, float] = defaultdict(float)
            for res in avail.values():
                for k, v in res.items():
                    freed[k] += v
            self._release(dict(freed))
        return pb.Empty()

    # ----------------------------------------------------------- spilling
    SPILL_HIGH = 0.9  # spill starts above this fraction of the budget
    SPILL_LOW = 0.7   # ... and runs down to this fraction

    def _maybe_spill(self):
        """Signal the spill thread when the store exceeds its budget
        (reference: LocalObjectManager::SpillObjectsOfSize,
        local_object_manager.h:41 — spilling happens on background IO, so
        the put/get handler threads never stall on the disk drain)."""
        if self._shm is None:
            return
        used, _ = self._shm.stats()
        if used > self._store_capacity * self.SPILL_HIGH:
            self._spill_event.set()

    def _spill_loop(self):
        while not self._stop.is_set():
            if not self._spill_event.wait(0.25):
                continue
            self._spill_event.clear()
            self._drain_to_low_water()

    def _drain_to_low_water(self, min_free_bytes: int = 0):
        """Spill LRU-cold objects until usage falls to the low watermark
        (or low enough that ``min_free_bytes`` fits — an object larger
        than the watermark slack must still be admittable; reference:
        plasma SpillObjectsOfSize takes the needed size). The lock is
        taken per victim so concurrent restores/pulls interleave with the
        drain instead of blocking for its whole duration."""
        target = min(self._store_capacity * self.SPILL_LOW,
                     max(self._store_capacity - min_free_bytes, 0))
        try:
            os.makedirs(self._spill_dir, exist_ok=True)
        except OSError:
            return
        while not self._stop.is_set():
            used, _ = self._shm.stats()
            if used <= target:
                break
            with self._spill_lock:
                oid = self._shm.coldest()
                if oid is None:
                    break
                data = self._shm.read(oid)
                if data is None:
                    self._shm.delete(oid)
                    continue
                path = os.path.join(self._spill_dir, oid)
                tmp = f"{path}.tmp.{os.getpid()}"
                try:
                    with open(tmp, "wb") as f:
                        f.write(data)
                    os.replace(tmp, path)
                except OSError:
                    logger.exception("spill write failed; stopping spill")
                    break
                self._spilled[oid] = (path, len(data))
                self._shm.delete(oid)
                mdefs.STORE_SPILLED.inc(tags=self._mtags)
                mdefs.STORE_SPILLED_BYTES.inc(len(data), tags=self._mtags)

    def _restore_spilled(self, oid_hex: str) -> Optional[bytes]:
        """Bring a spilled object back (reference:
        ObjectManager restore-from-external-storage). Returns the bytes, or
        None if this object was never spilled here."""
        with self._spill_lock:
            meta = self._spilled.get(oid_hex)
            if meta is None:
                return None
            path, _ = meta
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                self._spilled.pop(oid_hex, None)
                return None
            if self._shm is not None and \
                    self._shm.put(oid_hex, data) is not None:
                self._spilled.pop(oid_hex, None)
                try:
                    os.unlink(path)
                except OSError:
                    pass
        mdefs.STORE_RESTORED.inc(tags=self._mtags)
        self._maybe_spill()  # the restore itself may breach the high water
        return data

    # ------------------------------------------------------ memory monitor
    def _memory_usage_fraction(self) -> float:
        if self._mem_usage_file:
            try:
                with open(self._mem_usage_file) as f:
                    return float(f.read().strip() or 0.0)
            except (OSError, ValueError):
                return 0.0
        try:  # cgroup v2 limit, when one is set
            with open("/sys/fs/cgroup/memory.current") as f:
                cur = int(f.read())
            with open("/sys/fs/cgroup/memory.max") as f:
                mx = f.read().strip()
            if mx != "max":
                return cur / max(int(mx), 1)
        except (OSError, ValueError):
            pass
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, v = line.split(":", 1)
                    info[k] = int(v.strip().split()[0])
            return 1.0 - info["MemAvailable"] / max(info["MemTotal"], 1)
        except (OSError, ValueError, KeyError):
            return 0.0

    def _memory_monitor_loop(self):
        while not self._stop.wait(0.25):
            if self._memory_usage_fraction() < self._mem_threshold:
                continue
            if self._shed_memory():
                # Give the freed memory time to show up before re-checking.
                self._stop.wait(1.0)

    def _shed_memory(self) -> bool:
        """Kill the newest leased non-actor worker (newest-first mirrors the
        reference policy of shedding retriable work before long-running
        work; the node doesn't see TaskSpecs, so retriability itself is
        decided by the owner's retry budget on the crash-retry path)."""
        with self._pool_lock:
            busy = [w for w in self._workers.values()
                    if w.leased_for is not None and not w.is_actor_worker
                    and w.proc.poll() is None]
            if not busy:
                return False
            victim = max(busy, key=lambda w: w.busy_since)
        logger.warning(
            "memory usage above threshold %.2f: killing newest task worker "
            "%s (reference memory_monitor policy)",
            self._mem_threshold, victim.worker_id)
        try:
            victim.proc.kill()
        except Exception:  # noqa: BLE001
            return False
        self.oom_kills += 1
        mdefs.NODE_OOM_KILLS.inc(tags=self._mtags)
        return True

    # ------------------------------------------------------------ objects
    def _store_object(self, request) -> Optional[int]:
        """Seat one object in the local store; returns its size, or None
        when it could not be stored (capacity even after spilling) — the
        caller must NOT register a directory location for a dropped
        object, or readers would spin fetching something that isn't there.

        Backpressure (reference: plasma's create-request queue): a
        capacity failure spills down to the low watermark synchronously
        and retries once before giving up.
        """
        size = request.size or len(request.data)
        oid_hex = request.object_id.hex()
        if request.shm_name and self._shm is not None:
            # Zero-copy put: the client already created+sealed the segment;
            # only the metadata is registered (plasma Create/Seal protocol).
            if not self._seat_with_backpressure(
                    lambda: self._shm.register(oid_hex, request.shm_name,
                                               request.size), size):
                logger.warning("store full: rejecting register of %s "
                               "(%d bytes)", oid_hex[:12], size)
                # Nothing indexes the client-created segment now: unlink
                # it or it leaks in /dev/shm forever.
                from ray_tpu._private.shm import ShmClient

                ShmClient.unlink_segment(request.shm_name)
                mdefs.STORE_PUTS.inc(tags={**self._mtags,
                                           "outcome": "rejected"})
                return None
        elif self._shm is not None and request.data:
            if not self._seat_with_backpressure(
                    lambda: self._shm.put(oid_hex,
                                          request.data) is not None, size):
                logger.warning("store full: rejecting put of %s "
                               "(%d bytes)", oid_hex[:12], size)
                mdefs.STORE_PUTS.inc(tags={**self._mtags,
                                           "outcome": "rejected"})
                return None
        else:
            with self._obj_lock:
                self._objects[request.object_id] = request.data
        # Counted only once the object actually seated — rejected puts
        # must not inflate the store-fill byte series.
        mdefs.STORE_PUT_BYTES.inc(size, tags=self._mtags)
        mdefs.STORE_PUTS.inc(tags={**self._mtags, "outcome": "ok"})
        return size

    def _seat_with_backpressure(self, attempt, size: int,
                                retries: int = 5) -> bool:
        """Run ``attempt()`` with spill-down retries: concurrent writers
        can consume freed space between a drain and the retry, so one
        retry is not enough under sustained pressure (plasma queues
        create requests; this bounded loop is the collapsed analog)."""
        if attempt():
            return True
        if size > self._store_capacity:
            # Can NEVER fit: draining would evict the entire store to
            # disk on every retry without ever succeeding.
            return False
        for _ in range(retries):
            self._drain_to_low_water(min_free_bytes=size)
            if attempt():
                return True
        return False

    def PutObject(self, request, context):
        size = self._store_object(request)
        if size is not None:
            try:
                self.gcs.UpdateObjectLocation(pb.ObjectLocationUpdate(
                    object_id=request.object_id, node_id=self.node_id,
                    added=True, size=size))
            except Exception:  # noqa: BLE001
                pass
        self._maybe_spill()
        return pb.PutObjectReply(rejected=size is None)

    def PutObjectBatch(self, request, context):
        """Amortized small-object puts (the driver's put flusher batches
        inline payloads into one RPC instead of an RPC per object; the
        directory registration rides one batched GCS RPC too)."""
        batch = pb.ObjectLocationBatch()
        rejected = []
        for item in request.items:
            size = self._store_object(item)
            rejected.append(size is None)
            if size is None:
                continue  # rejected at capacity: no location to register
            batch.updates.append(pb.ObjectLocationUpdate(
                object_id=item.object_id, node_id=self.node_id,
                added=True, size=size))
        try:
            self.gcs.UpdateObjectLocationsBatch(batch)
        except Exception:  # noqa: BLE001
            pass
        self._maybe_spill()
        return pb.PutObjectBatchReply(rejected=rejected)

    def GetObject(self, request, context):
        reply = self._get_object_inner(request)
        mdefs.STORE_GETS.inc(tags={
            **self._mtags, "outcome": "hit" if reply.found else "miss"})
        return reply

    def _get_object_inner(self, request):
        oid_hex = request.object_id.hex()
        if self._shm is not None:
            meta = self._shm.get(oid_hex)
            if meta is None and oid_hex in self._spilled:
                if request.metadata_only:
                    # Report presence without paying the restore.
                    size = self._spilled.get(oid_hex, (None, 0))[1]
                    return pb.GetObjectReply(found=True, size=size)
                data = self._restore_spilled(oid_hex)
                if data is not None:
                    meta = self._shm.get(oid_hex)
                    if meta is None:  # restore couldn't re-seat it in shm
                        return pb.GetObjectReply(found=True, data=data)
            if meta is not None:
                name, size = meta
                if request.metadata_only:
                    return pb.GetObjectReply(found=True, size=size)
                return pb.GetObjectReply(found=True, shm_name=name, size=size)
        with self._obj_lock:
            data = self._objects.get(request.object_id)
        if data is None:
            return pb.GetObjectReply(found=False)
        if request.metadata_only:
            return pb.GetObjectReply(found=True, size=len(data))
        return pb.GetObjectReply(found=True, data=data)

    def GetObjectsMeta(self, request, context):
        """Batched local readiness (reference: plasma Contains). One RPC
        answers every object a wait() is watching on this node."""
        found = []
        for oid in request.object_ids:
            hexid = oid.hex()
            ok = False
            if self._shm is not None:
                ok = self._shm.contains(hexid) or hexid in self._spilled
            if not ok:
                with self._obj_lock:
                    ok = oid in self._objects
            found.append(ok)
        return pb.GetObjectsMetaReply(found=found)

    def _read_object_bytes(self, object_id: bytes) -> Optional[bytes]:
        if self._shm is not None:
            data = self._shm.read(object_id.hex())
            if data is not None:
                return data
            # Spilled: serve straight from disk without churning the store
            # (remote pulls don't need the object resident locally).
            with self._spill_lock:
                meta = self._spilled.get(object_id.hex())
                if meta is not None:
                    try:
                        with open(meta[0], "rb") as f:
                            return f.read()
                    except OSError:
                        pass
        with self._obj_lock:
            return self._objects.get(object_id)

    def PullObject(self, request, context):
        """Chunked streaming transfer (reference: ObjectManager 64MB chunks,
        object_manager.h:117). Outbound streams are capped (PushManager
        analog, push_manager.h:30): a hot object fanned out to many nodes
        queues behind the slot limit instead of saturating every handler
        thread at once."""
        data = self._read_object_bytes(request.object_id)
        if data is None:
            yield pb.ObjectChunk(object_id=request.object_id, found=False,
                                 eof=True)
            return
        if not self._push_slots.acquire(timeout=60.0):
            # Saturated for a full minute: fail the pull; the client
            # retries another location or re-requests.
            yield pb.ObjectChunk(object_id=request.object_id, found=False,
                                 eof=True)
            return
        try:
            total = len(data)
            for off in range(0, max(total, 1), CHUNK_SIZE):
                chunk = data[off:off + CHUNK_SIZE]
                yield pb.ObjectChunk(object_id=request.object_id,
                                     total_size=total, offset=off,
                                     data=chunk, found=True,
                                     eof=off + CHUNK_SIZE >= total)
        finally:
            self._push_slots.release()

    def FreeObjects(self, request, context):
        with self._obj_lock:
            for oid in request.object_ids:
                self._objects.pop(oid, None)
        batch = pb.ObjectLocationBatch()
        for oid in request.object_ids:
            if self._shm is not None:
                self._shm.delete(oid.hex())
            with self._spill_lock:
                meta = self._spilled.pop(oid.hex(), None)
            if meta is not None:
                try:
                    os.unlink(meta[0])
                except OSError:
                    pass
            batch.updates.append(pb.ObjectLocationUpdate(
                object_id=oid, node_id=self.node_id, added=False))
        try:
            self.gcs.UpdateObjectLocationsBatch(batch)
        except Exception:  # noqa: BLE001
            pass
        return pb.Empty()

    # ------------------------------------------------------------ lifecycle
    def shutdown(self, graceful: bool = True):
        """Stop the node. ``graceful=False`` simulates a node crash: no drain
        notification, so the GCS health checker must discover the death."""
        self._stop.set()
        try:
            # Close the fastpath object plane first: a zombie listener
            # would keep accepting put registrations for a dead node.
            self._fast.close()
        except Exception:  # noqa: BLE001
            pass
        if graceful:
            try:
                self.gcs.DrainNode(pb.DrainNodeRequest(node_id=self.node_id),
                                   timeout=2)
            except Exception:  # noqa: BLE001
                pass
        # Kill twice with a grace gap so workers mid-spawn in the prestart
        # thread are also reaped.
        for _ in range(2):
            with self._pool_lock:
                workers = list(self._workers.values())
            for w in workers:
                try:
                    w.proc.terminate()
                except Exception:  # noqa: BLE001
                    pass
            time.sleep(0.1)
        if self._agent_proc is not None:
            try:
                self._agent_proc.terminate()
            except Exception:  # noqa: BLE001
                pass
        self._server.stop(grace=0.2)
        if self._shm is not None:
            try:
                self._shm.close()
            except Exception:  # noqa: BLE001
                pass
        import shutil

        shutil.rmtree(self._spill_dir, ignore_errors=True)


class _DummyProc:
    def __init__(self, pid: int):
        self.pid = pid

    def poll(self):
        try:
            os.kill(self.pid, 0)
            return None
        except OSError:
            return 1

    def terminate(self):
        try:
            os.kill(self.pid, 15)
        except OSError:
            pass


def main():  # pragma: no cover - run as subprocess
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--num-cpus", type=float, default=float(os.cpu_count() or 4))
    parser.add_argument("--num-tpus", type=float, default=-1.0,
                        help="-1 = auto-detect, 0 = explicitly none")
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--labels", default="{}")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    import json

    resources = {"CPU": args.num_cpus}
    labels = json.loads(args.labels)
    if args.num_tpus > 0:
        resources["TPU"] = args.num_tpus
    elif args.num_tpus < 0:
        # Auto-detect TPU hardware (reference TPUAcceleratorManager
        # detection, tpu.py:47-118): contributes TPU chips, the
        # accelerator_type marker, the per-slice TPU-<type>-head resource
        # (exactly one coordination actor per slice), and the ICI
        # topology labels the slice-aware PACK/label policies consume.
        try:
            from ray_tpu._private.accelerators.tpu import \
                TPUAcceleratorManager

            resources.update(TPUAcceleratorManager.node_resources())
            acc = TPUAcceleratorManager.accelerator_type()
            pod = TPUAcceleratorManager.pod_name()
            if pod:
                labels.setdefault("tpu-slice", pod)
            if acc:
                labels.setdefault("tpu-pod-type", acc)
        except Exception:  # noqa: BLE001 — no TPU on this host
            logger.exception(
                "TPU auto-detection failed; registering without TPU "
                "resources (pass --num-tpus to set them explicitly)")
    resources.update(json.loads(args.resources))
    nm = NodeManager(args.gcs_address, port=args.port, resources=resources,
                     labels=labels)
    print(f"NODE_PORT={nm.port}", flush=True)
    print(f"NODE_ID={nm.node_id}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        nm.shutdown()


if __name__ == "__main__":  # pragma: no cover
    main()
