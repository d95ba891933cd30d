"""Train/AIR configuration dataclasses.

Reference: ``python/ray/air/config.py`` (ScalingConfig/RunConfig/FailureConfig/
CheckpointConfig). TPU-native addition: ``ScalingConfig.topology`` describes
the per-worker chip ask (e.g. "v5e-8") and ``mesh`` the parallelism layout the
backend should build — the reference expresses neither because NCCL ranks are
topology-blind.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from ray_tpu.parallel import MeshConfig


@dataclasses.dataclass
class ScalingConfig:
    """How many workers and what each one owns.

    ``num_workers`` is the number of *processes* (actors); with TPU, each
    worker owns ``tpus_per_worker`` chips and all workers jointly run one
    SPMD program over the global mesh.
    """

    num_workers: int = 1
    # Elastic lower bound (reference: Train v2 elastic training): after a
    # failure the controller restarts with as many workers as the cluster
    # can currently supply, as long as it's at least this. None = rigid.
    min_workers: Optional[int] = None
    use_tpu: bool = False
    tpus_per_worker: Optional[float] = None
    cpus_per_worker: float = 1.0
    resources_per_worker: Optional[Dict[str, float]] = None
    topology: Optional[str] = None       # e.g. "v5e-8": slice type ask
    mesh: Optional[MeshConfig] = None    # parallelism layout over all chips
    placement_strategy: str = "PACK"
    # Form a real multi-process jax.distributed group across the worker
    # actors (worker 0 hosts the coordinator service; the address is also
    # published to the GCS KV). Off by default: single-host workers sharing
    # one jax client don't need it.
    jax_distributed: bool = False

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", self.cpus_per_worker)
        if self.use_tpu:
            res.setdefault("TPU", self.tpus_per_worker or 1.0)
        return res

    @property
    def total_workers(self) -> int:
        return self.num_workers


@dataclasses.dataclass
class FailureConfig:
    """Reference: ``air/config.py::FailureConfig``.

    ``max_failures`` governs USER exceptions only (the train loop
    raising). Infrastructure failures — worker death, hung collectives,
    lapsed heartbeats — have their own budget (``RAY_TPU_MAX_RESTARTS``),
    preemptions theirs (``RAY_TPU_MAX_PREEMPTIONS``), and worker-set
    resizes theirs (``RAY_TPU_MAX_RESIZES``); see
    ``ray_tpu/train/elastic.py`` for the full classification.
    """

    max_failures: int = 0  # 0 = no retries, -1 = infinite
    # Per-step watchdog: if no worker reports for this long after the
    # first report, the attempt is declared hung (retryable under the
    # restart budget). None reads RAY_TPU_STEP_WATCHDOG_S; 0 disables.
    # Before the first report the deadline is 10x (compile headroom).
    watchdog_s: Optional[float] = None
    # Fatal-NaN guard: this many CONSECUTIVE reports with a non-finite
    # "loss" ends the run as FATAL (restarting would replay the same
    # divergence). None reads RAY_TPU_NAN_FATAL_REPORTS; 0 disables.
    nan_fatal_reports: Optional[int] = None


@dataclasses.dataclass
class CheckpointConfig:
    """Reference: ``air/config.py::CheckpointConfig`` (top-k retention)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0
    # Persist checkpoints on a background thread (orbax-style: one write
    # in flight; the trainer joins it before restarts/results).
    async_write: bool = False


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    verbose: int = 1

    def __post_init__(self):
        if self.failure_config is None:
            self.failure_config = FailureConfig()
        if self.checkpoint_config is None:
            self.checkpoint_config = CheckpointConfig()


@dataclasses.dataclass
class Result:
    """Reference: ``air/result.py``."""

    metrics: Optional[Dict[str, Any]]
    checkpoint: Optional[Any]
    path: Optional[str]
    error: Optional[BaseException] = None
    metrics_history: Optional[list] = None

    @property
    def best_checkpoints(self):
        return [(self.checkpoint, self.metrics)] if self.checkpoint else []
