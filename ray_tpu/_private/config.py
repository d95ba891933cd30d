"""Framework configuration: typed defaults, env overrides, JSON system-config.

Re-design of the reference config system (reference: ``src/ray/common/ray_config_def.h``
— 220 ``RAY_CONFIG(type, name, default)`` macros, overridable via env ``RAY_<name>``
or the ``_system_config`` JSON passed to ``ray.init``). Here a config entry is a
dataclass field; overrides are resolved at access time in priority order:

    1. explicit ``_system_config`` dict passed to :func:`ray_tpu.init`
    2. environment variable ``RAY_TPU_<name>`` (and ``RAY_<name>`` for parity)
    3. the coded default

Booleans accept 0/1/true/false; everything else is parsed with the field's type.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict


@dataclasses.dataclass
class _ConfigDefaults:
    # --- object store -----------------------------------------------------
    # Objects larger than this are promoted from the in-process memory store
    # to the shared-memory store (reference: core_worker store providers,
    # 100KB threshold).
    max_direct_call_object_size: int = 100 * 1024
    # Default shm store size as a fraction of system memory if not given.
    object_store_memory_fraction: float = 0.3
    object_store_memory: int = 0  # 0 = auto from fraction, capped below
    object_store_memory_cap: int = 20 * 2**30
    # Chunk size for node-to-node object transfer.
    object_transfer_chunk_size: int = 64 * 2**20
    # Seconds an unreferenced primary copy stays before eviction is allowed.
    object_store_full_delay_ms: int = 10

    # --- scheduler --------------------------------------------------------
    # Hybrid policy: pack onto nodes until utilization crosses this threshold,
    # then spread (reference: hybrid_scheduling_policy.cc:99 — 0.5).
    scheduler_spread_threshold: float = 0.5
    # Max tasks in flight per lease (lease reuse).
    max_tasks_in_flight_per_worker: int = 10
    worker_lease_timeout_ms: int = 500

    # --- worker pool ------------------------------------------------------
    num_workers_soft_limit: int = 0  # 0 = num_cpus
    worker_register_timeout_seconds: int = 60
    idle_worker_killing_time_threshold_ms: int = 1000
    enable_worker_prestart: bool = True

    # --- health / failure detection --------------------------------------
    # Reference: gcs_health_check_manager.h:45-62.
    health_check_initial_delay_ms: int = 5000
    health_check_period_ms: int = 3000
    health_check_timeout_ms: int = 10000
    health_check_failure_threshold: int = 5

    # --- retries / recovery ----------------------------------------------
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    lineage_pinning_enabled: bool = True
    max_lineage_bytes: int = 1 * 2**30

    # --- rpc --------------------------------------------------------------
    rpc_connect_timeout_s: float = 10.0
    rpc_call_timeout_s: float = 120.0
    rpc_retry_base_delay_ms: int = 100
    rpc_retry_max_delay_ms: int = 5000
    rpc_max_retries: int = 5
    # Deterministic fault injection, format "method:prob[,method:prob...]"
    # (reference: src/ray/rpc/rpc_chaos.cc, env RAY_testing_rpc_failure).
    testing_rpc_failure: str = ""

    # --- gcs --------------------------------------------------------------
    gcs_storage_path: str = ""  # "" = in-memory; path = file-backed persistence
    gcs_pubsub_poll_timeout_s: float = 30.0

    # --- task events / tracing -------------------------------------------
    task_events_report_interval_ms: int = 1000
    task_events_max_buffer_size: int = 10000
    enable_timeline: bool = True

    # --- metrics ----------------------------------------------------------
    metrics_report_interval_ms: int = 5000
    # Seconds of device trace the XLA monitor keeps of a stalled stretch
    # (engine ticks, prefill batches or train steps several times over
    # their own median), started while it runs; 0 = never
    # (``RAY_TPU_stall_capture_s=3`` in a replica's environment).
    stall_capture_s: float = 0.0

    # --- memory monitor ---------------------------------------------------
    memory_usage_threshold: float = 0.95
    memory_monitor_refresh_ms: int = 250

    # --- TPU --------------------------------------------------------------
    # Treat TPU chips as first-class schedulable resources.
    tpu_chips_per_host_default: int = 4
    # ICI slice label prefix used for slice-aware placement groups.
    tpu_slice_resource_prefix: str = "TPU-slice"


_TRUE = {"1", "true", "True", "TRUE", "yes", "on"}
_FALSE = {"0", "false", "False", "FALSE", "no", "off"}


class RayTpuConfig:
    """Accessor resolving (system_config > env > default) per field."""

    def __init__(self):
        self._defaults = _ConfigDefaults()
        self._system_config: Dict[str, Any] = {}
        self._fields = {f.name: f.type for f in dataclasses.fields(_ConfigDefaults)}

    def initialize(self, system_config: Dict[str, Any] | str | None):
        if system_config is None:
            system_config = {}
        if isinstance(system_config, str):
            system_config = json.loads(system_config) if system_config else {}
        unknown = set(system_config) - set(self._fields)
        if unknown:
            raise ValueError(f"Unknown _system_config keys: {sorted(unknown)}")
        self._system_config = dict(system_config)

    def _coerce(self, name: str, raw: Any) -> Any:
        default = getattr(self._defaults, name)
        ty = type(default)
        if isinstance(raw, ty) and not (ty is int and isinstance(raw, bool)):
            return raw
        if ty is bool:
            s = str(raw)
            if s in _TRUE:
                return True
            if s in _FALSE:
                return False
            raise ValueError(f"Cannot parse bool config {name}={raw!r}")
        return ty(raw)

    def __getattr__(self, name: str) -> Any:
        fields = object.__getattribute__(self, "_fields")
        if name not in fields:
            raise AttributeError(name)
        sysconf = object.__getattribute__(self, "_system_config")
        if name in sysconf:
            return self._coerce(name, sysconf[name])
        for prefix in ("RAY_TPU_", "RAY_"):
            env = os.environ.get(prefix + name)
            if env is not None:
                return self._coerce(name, env)
        return getattr(object.__getattribute__(self, "_defaults"), name)

    def dump(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in self._fields}


GLOBAL_CONFIG = RayTpuConfig()
