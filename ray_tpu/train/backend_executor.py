"""Worker group + backend executor for distributed training.

Reference call stack (SURVEY.md §3.4): ``BackendExecutor.start``
(``train/_internal/backend_executor.py:142``) creates a placement group,
spawns N worker actors (``_internal/worker_group.py``), shares accelerator
visibility among colocated workers, assigns ranks, runs
``train_loop_per_worker`` and polls a session queue for results.

TPU-native differences:

* ``JaxBackend.on_start`` is where multi-host SPMD bootstrap happens
  (``jax.distributed.initialize`` with a coordinator chosen from worker 0 —
  the analog of the reference's MASTER_ADDR + ``dist.init_process_group``,
  ``train/torch/config.py:153``). In single-process runtimes it is a no-op.
* Accelerator visibility shares ``TPU_VISIBLE_CHIPS`` (the reference shares
  ``CUDA_VISIBLE_DEVICES``, ``backend_executor.py:278``).

Elastic failure detection (reference: Train v2 worker-group health checks
+ the GCS health-check manager): every ``poll()`` is also a liveness
probe. Three independent channels feed it:

1. **actor death** — a dead worker's poll raises ``ActorDiedError``
   (annotated with the failed rank);
2. **heartbeats** — each worker runs a heartbeat thread that stamps a
   timestamp returned by ``poll()`` AND pushes it through the GCS KV
   (``__train__`` namespace) so a controller can see lapses even when the
   actor channel is slow; a lapse past ``RAY_TPU_TRAIN_HEARTBEAT_TTL_S``
   raises ``WorkerHangError(kind="heartbeat")``;
3. **step progress** — ``progress_ts`` moves on every ``train.report``;
   the trainer's per-step watchdog turns a stall into
   ``WorkerHangError(kind="watchdog")`` (hung collective).

The chaos harness (``_private/chaos.py``) can kill a worker at a step
boundary, drop/delay heartbeats, or wedge a step — each detection path
above is exercised by a real injected fault in tests.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.train import session as session_mod
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import ScalingConfig

logger = logging.getLogger(__name__)

# Namespace for worker heartbeat records pushed through the GCS KV.
TRAIN_KV_NS = "__train__"


def _hb_period_s() -> float:
    return float(os.environ.get("RAY_TPU_TRAIN_HEARTBEAT_S", 0.5))


def _hb_ttl_s() -> float:
    return float(os.environ.get("RAY_TPU_TRAIN_HEARTBEAT_TTL_S", 5.0))


def _teardown_join_s() -> float:
    return float(os.environ.get("RAY_TPU_TEARDOWN_JOIN_S", 5.0))


class TrainWorker:
    """Actor hosting one training process (reference: ``RayTrainWorker``)."""

    def __init__(self, world_rank: int, world_size: int, local_rank: int,
                 local_world_size: int, env: Optional[Dict[str, str]] = None,
                 group_id: str = ""):
        self.rank = world_rank
        self.group_id = group_id
        for k, v in (env or {}).items():
            os.environ[k] = v
        self._ctx = session_mod.TrainContext(
            world_rank, world_size, local_rank, local_world_size)
        self._session: Optional[session_mod._Session] = None
        self._lock = threading.Lock()
        self._hb_ts: Optional[float] = None

    def setup(self, env: Dict[str, str]):
        for k, v in env.items():
            os.environ[k] = v
        return True

    def node_ip(self) -> str:
        """Routable address of this worker's host — the coordinator must be
        reachable from every other host, so loopback is only the fallback."""
        import socket

        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect(("8.8.8.8", 80))  # no packet sent; routing only
                return s.getsockname()[0]
        except OSError:
            return "127.0.0.1"

    def reserve_port(self) -> int:
        """Free port on this worker's host for the coordinator service."""
        import socket

        with socket.socket() as s:
            s.bind(("", 0))
            return s.getsockname()[1]

    def init_jax_distributed(self, coordinator: str, num_processes: int,
                             timeout_s: Optional[float] = None):
        """Join the jax.distributed group (reference analog: MASTER_ADDR +
        ``dist.init_process_group``, ``train/torch/config.py:153``). Worker
        0 hosts the coordinator service; every process must call in before
        any jax computation runs in it."""
        import jax

        kwargs = {}
        if timeout_s is not None:
            # jax's initialization_timeout is in whole seconds.
            kwargs["initialization_timeout"] = max(int(timeout_s), 1)
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=self.rank, **kwargs)
        except RuntimeError as e:
            if "already" not in str(e).lower():
                raise
        return jax.process_index()

    # ------------------------------------------------------- liveness
    def _heartbeat_loop(self, s: "session_mod._Session") -> None:
        """Stamp liveness every period until the session ends. Beats are
        surfaced two ways: the ``_hb_ts`` field returned by ``poll()``
        (actor channel) and a GCS KV record (``__train__`` namespace) so
        lapses are visible cluster-wide even if polls stall. The chaos
        ``train_heartbeat`` site can drop or delay beats."""
        from ray_tpu._private import chaos

        period = _hb_period_s()
        kv = None
        try:
            from ray_tpu.experimental import internal_kv as kv
        except Exception:  # noqa: BLE001 — no runtime in this process
            kv = None
        key = f"hb/{self.group_id}/{self.rank:05d}"
        while not s.finished.wait(period):
            if s.stop.is_set():
                return
            d = chaos.inject("train_heartbeat", rank=self.rank) or {}
            if d.get("delay_s"):
                time.sleep(float(d["delay_s"]))
            if d.get("drop"):
                continue
            now = time.time()
            self._hb_ts = now
            if kv is not None:
                try:
                    import json

                    kv.internal_kv_put(
                        key, json.dumps({"ts": now, "rank": self.rank,
                                         "pid": os.getpid()}).encode(),
                        overwrite=True, namespace=TRAIN_KV_NS)
                except Exception:  # noqa: BLE001 — KV push is best-effort
                    kv = None  # stop retrying a dead channel this session

    def stop(self) -> bool:
        """Cooperative teardown: flag the running session to unwind at its
        next report (elastic restart/resize)."""
        with self._lock:
            s = self._session
        if s is not None:
            s.stop.set()
        return True

    def run(self, fn: Callable, config: Optional[Dict[str, Any]],
            restore_checkpoint_path: Optional[str],
            run_dir: Optional[str] = None,
            dataset_shards: Optional[Dict[str, Any]] = None):
        """Run the user train loop to completion (blocking actor call)."""
        from ray_tpu._private import chaos

        ckpt = (Checkpoint(restore_checkpoint_path)
                if restore_checkpoint_path else None)
        s = session_mod._Session(self._ctx, ckpt, run_dir=run_dir,
                                 dataset_shards=dataset_shards,
                                 group_id=self.group_id)
        with self._lock:
            self._session = s
        session_mod._set_session(s)
        self._hb_ts = time.time()
        threading.Thread(target=self._heartbeat_loop, args=(s,),
                         daemon=True,
                         name=f"train-hb-{self.rank}").start()
        try:
            s.result = fn(config) if config is not None else fn()
            return s.result
        finally:
            if s.checkpoint_plane is not None and not chaos.process_dying():
                # Join in-flight async saves so a committed manifest is
                # durable before the controller sees this worker finish.
                # Skipped when unwinding a chaos-injected kill: a dead
                # process would never have flushed either.
                try:
                    s.checkpoint_plane.close()
                except Exception:  # noqa: BLE001 — loop outcome wins
                    logger.exception("checkpoint plane close failed")
            s.ledger.close()  # freeze the attempt's goodput wall clock
            s.finished.set()
            session_mod._set_session(None)

    def poll(self) -> Dict[str, Any]:
        """Drain pending reports (runs concurrently with ``run``)."""
        with self._lock:
            s = self._session
        if s is None:
            return {"reports": [], "finished": False}
        reports = []
        while True:
            try:
                r = s.reports.get_nowait()
            except queue.Empty:
                break
            # Checkpoints cross the actor boundary as paths.
            if r.get("checkpoint") is not None:
                r = dict(r, checkpoint_path=r.pop("checkpoint").path)
            reports.append(r)
        return {"reports": reports, "finished": s.finished.is_set(),
                "heartbeat_ts": self._hb_ts,
                "progress_ts": s.progress_ts, "last_step": s.last_step,
                # Goodput ledger snapshot (components sum to wall_s):
                # the controller differences consecutive snapshots into
                # ray_tpu_train_goodput_seconds_total{component}.
                "ledger": s.ledger.snapshot()}


class WorkerGroup:
    """Reference: ``train/_internal/worker_group.py``."""

    def __init__(self, scaling: ScalingConfig, group_id: str = ""):
        self.scaling = scaling
        self.group_id = group_id or uuid.uuid4().hex[:8]
        worker_cls = ray_tpu.remote(TrainWorker)
        n = scaling.num_workers
        self.workers = [
            worker_cls.options(
                num_cpus=scaling.worker_resources().get("CPU", 1),
                resources={k: v for k, v in scaling.worker_resources().items()
                           if k not in ("CPU", "GPU")},
                max_concurrency=3,  # run() + poll()/stop() concurrently
            ).remote(rank, n, rank, n, group_id=self.group_id)
            for rank in range(n)
        ]

    def execute(self, method: str, *args, **kwargs) -> List[Any]:
        return ray_tpu.get(
            [getattr(w, method).remote(*args, **kwargs) for w in self.workers]
        )

    def execute_per_rank(self, method: str, *args, **kwargs) -> List[Any]:
        """Like :meth:`execute`, but a failure is attributed: raises the
        first failing rank's exception with ``failed_rank`` set on it.
        The happy path stays ONE batched get (this runs ~50Hz under the
        controller's poll loop); per-ref resolution only happens after
        the batch failed, when the refs are already local."""
        refs = [getattr(w, method).remote(*args, **kwargs)
                for w in self.workers]
        try:
            return ray_tpu.get(refs)
        except BaseException:  # noqa: BLE001 — attributed below
            pass
        out = []
        for rank, ref in enumerate(refs):
            try:
                out.append(ray_tpu.get(ref))
            except BaseException as e:  # noqa: BLE001 — annotate + re-raise
                try:
                    e.failed_rank = rank
                except Exception:  # noqa: BLE001 — frozen exception type
                    pass
                raise
        return out

    def execute_async(self, method: str, *args, **kwargs):
        return [getattr(w, method).remote(*args, **kwargs) for w in self.workers]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []


class JaxBackend:
    """Backend plugin (reference ABC: ``train/backend.py``)."""

    # Coordinator bootstrap retries: a stale/raced port rebinds to a fresh
    # one with exponential backoff before the environment is declared
    # unable to form a jax.distributed group.
    COORD_ATTEMPTS = 3
    COORD_BACKOFF_S = 0.5

    def on_start(self, worker_group: WorkerGroup, scaling: ScalingConfig):
        # Multi-host bootstrap: worker 0 is the jax.distributed coordinator.
        # In the in-process runtime all workers share one jax client, so the
        # only thing to share is TPU visibility (reference shares
        # CUDA_VISIBLE_DEVICES across colocated workers).
        env = {"RAY_TPU_TRAIN_WORLD_SIZE": str(scaling.num_workers)}
        worker_group.execute("setup", env)
        if scaling.jax_distributed and scaling.num_workers > 1:
            self._bootstrap_jax_distributed(worker_group, scaling)

    def _bootstrap_jax_distributed(self, worker_group: WorkerGroup,
                                   scaling: ScalingConfig) -> None:
        attempts = int(os.environ.get("RAY_TPU_JAX_COORD_ATTEMPTS",
                                      self.COORD_ATTEMPTS))
        w0 = worker_group.workers[0]
        host = ray_tpu.get(w0.node_ip.remote())
        last_err: Optional[BaseException] = None
        for attempt in range(attempts):
            # Fresh port every attempt: the dominant transient failure is
            # a coordinator port that raced another bind or lingers in
            # TIME_WAIT from a previous (failed) group.
            port = ray_tpu.get(w0.reserve_port.remote())
            coordinator = f"{host}:{port}"
            try:
                # Published for observability and late joiners (elastic
                # restarts re-read it) — the KV is the MASTER_ADDR channel.
                from ray_tpu._private import worker as _worker_mod
                from ray_tpu.protobuf import ray_tpu_pb2 as pb

                _worker_mod.global_worker().core.gcs.KvPut(pb.KvRequest(
                    ns="train", key=f"coordinator/{id(worker_group)}",
                    value=coordinator.encode(), overwrite=True))
            except Exception:  # noqa: BLE001 — local mode has no GCS
                pass
            try:
                # Bounded join: without a timeout a coordinator that never
                # comes up (sandboxed networking, firewalled port) hangs
                # the whole bootstrap instead of reaching the retry path.
                timeout_s = float(os.environ.get(
                    "RAY_TPU_JAX_COORD_TIMEOUT_S", 60.0))
                ranks = worker_group.execute(
                    "init_jax_distributed", coordinator,
                    scaling.num_workers, timeout_s)
                logger.info("jax.distributed group formed: coordinator=%s "
                            "ranks=%s", coordinator, ranks)
                return
            except Exception as e:  # noqa: BLE001 — bind/timeout/raced port
                last_err = e
                backoff = self.COORD_BACKOFF_S * (2 ** attempt)
                logger.warning(
                    "jax.distributed bootstrap attempt %d/%d failed on "
                    "%s (%s); rebinding coordinator port and retrying "
                    "in %.1fs", attempt + 1, attempts, coordinator, e,
                    backoff)
                time.sleep(backoff)
        raise exceptions.JaxDistributedBootstrapError(
            f"could not form a jax.distributed group after {attempts} "
            f"coordinator rebind attempts: {last_err}")

    def on_shutdown(self, worker_group: WorkerGroup):
        pass


class BackendExecutor:
    """Reference: ``train/_internal/backend_executor.py:69``."""

    def __init__(self, scaling: ScalingConfig, backend: Optional[JaxBackend] = None):
        self.scaling = scaling
        self.backend = backend or JaxBackend()
        self.worker_group: Optional[WorkerGroup] = None
        self._training_started_at: Optional[float] = None
        # rank -> (newest heartbeat stamp observed, controller-monotonic
        # time it changed) — the basis for skew-proof lapse detection.
        self._hb_seen: Dict[int, Tuple[float, float]] = {}

    @property
    def group_id(self) -> str:
        return self.worker_group.group_id if self.worker_group else ""

    def start(self):
        self.worker_group = WorkerGroup(self.scaling)
        self.backend.on_start(self.worker_group, self.scaling)

    def start_training(self, train_fn: Callable,
                       config: Optional[Dict[str, Any]],
                       restore_checkpoint_path: Optional[str],
                       run_dir: Optional[str] = None,
                       datasets: Optional[List[Dict[str, Any]]] = None
                       ) -> List[Any]:
        """``datasets`` is PER-RANK: element ``i`` is rank i's
        ``{name: DataIterator}`` map of disjoint streaming_split shards
        (every other start_training arg is identical across ranks)."""
        assert self.worker_group is not None
        refs = []
        for rank, w in enumerate(self.worker_group.workers):
            shards = datasets[rank] if datasets else None
            refs.append(w.run.remote(train_fn, config,
                                     restore_checkpoint_path, run_dir,
                                     shards))
        self._hb_seen.clear()
        self._training_started_at = time.monotonic()
        return refs

    def poll(self) -> List[Dict[str, Any]]:
        """Drain worker reports AND probe liveness: raises
        ``ActorDiedError`` (with ``failed_rank``) for a dead worker, and
        ``WorkerHangError(kind="heartbeat")`` when a live-looking worker's
        heartbeats lapsed past ``RAY_TPU_TRAIN_HEARTBEAT_TTL_S``."""
        assert self.worker_group is not None
        polls = self.worker_group.execute_per_rank("poll")
        self._check_heartbeats(polls)
        return polls

    def _check_heartbeats(self, polls: List[Dict[str, Any]]) -> None:
        """Staleness is measured CONTROLLER-side: a rank lapses when the
        heartbeat stamp it reports stops *changing* for longer than the
        TTL on the controller's monotonic clock — never by differencing
        two hosts' wall clocks, which would declare every healthy worker
        hung under cross-host clock skew greater than the TTL."""
        ttl = _hb_ttl_s()
        if ttl <= 0 or self._training_started_at is None:
            return
        mono = time.monotonic()
        for rank, p in enumerate(polls):
            if p.get("finished") or "heartbeat_ts" not in p:
                continue
            hb = float(p.get("heartbeat_ts") or 0.0)
            seen = self._hb_seen.get(rank)
            if seen is None or hb > seen[0]:
                self._hb_seen[rank] = (hb, mono)
                continue
            if mono - seen[1] > ttl:
                # Second opinion from the GCS KV mirror before declaring
                # a lapse (the KV may be ahead when the actor path is
                # backed up) — only probed once the actor stamp is stale,
                # so the common case costs no KV round-trip.
                kv_hb = self._kv_heartbeat(rank)
                if kv_hb > seen[0]:
                    self._hb_seen[rank] = (kv_hb, mono)
                    continue
                raise exceptions.WorkerHangError(
                    f"rank {rank} heartbeats stalled "
                    f"{mono - seen[1]:.1f}s (TTL {ttl:.1f}s)",
                    rank=rank, kind="heartbeat")

    def _kv_heartbeat(self, rank: int) -> float:
        try:
            import json

            from ray_tpu.experimental import internal_kv as kv

            raw = kv.internal_kv_get(
                f"hb/{self.group_id}/{rank:05d}", namespace=TRAIN_KV_NS)
            return float(json.loads(raw)["ts"]) if raw else 0.0
        except Exception:  # noqa: BLE001 — KV probe is best-effort
            return 0.0

    def shutdown(self):
        if self.worker_group is not None:
            group_id = self.worker_group.group_id
            try:
                self.backend.on_shutdown(self.worker_group)
            except Exception:  # noqa: BLE001 — teardown must proceed
                logger.exception("backend on_shutdown failed")
            # Cooperative stop BEFORE the kill: in the in-process runtime
            # a killed actor's run() thread survives the kill, so flag its
            # session (shared memory) and, after the kill, wait for the
            # loop to unwind — zombie steps must not race the next
            # attempt's checkpoint stream.
            stopped = session_mod.stop_local_sessions(group_id)
            self.worker_group.shutdown()
            if stopped:
                session_mod.join_local_sessions(group_id,
                                                _teardown_join_s())
            self._drop_heartbeat_records(group_id)
            self.worker_group = None
            self._training_started_at = None
            self._hb_seen.clear()

    @staticmethod
    def _drop_heartbeat_records(group_id: str) -> None:
        """GC this generation's ``hb/<group_id>/*`` KV records — every
        elastic restart mints a fresh group_id, so without the sweep a
        long-lived cluster accumulates stale heartbeat keys forever."""
        try:
            from ray_tpu.experimental import internal_kv as kv

            for key in kv.internal_kv_list(f"hb/{group_id}/",
                                           namespace=TRAIN_KV_NS):
                kv.internal_kv_del(key, namespace=TRAIN_KV_NS)
        except Exception:  # noqa: BLE001 — KV gc is best-effort
            pass
