"""Runtime context: introspection of the current job/task/actor/node.

Re-design of the reference (reference: ``python/ray/runtime_context.py``).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from ray_tpu._private import worker as _worker


class RuntimeContext:
    @property
    def _core(self):
        return _worker.global_worker().core

    def get_job_id(self) -> str:
        return self._core.job_id.hex()

    def get_node_id(self) -> str:
        return self._core.node_id.hex()

    def get_task_id(self) -> Optional[str]:
        from ray_tpu._private.runtime.local import current_task_context

        ctx = current_task_context()
        return ctx.task_id.hex() if ctx else None

    def get_actor_id(self) -> Optional[str]:
        from ray_tpu._private.runtime.local import current_task_context

        ctx = current_task_context()
        return ctx.actor_id.hex() if ctx and ctx.actor_id else None

    def get_actor_name(self) -> Optional[str]:
        from ray_tpu._private.runtime.local import current_task_context

        ctx = current_task_context()
        if ctx is None or ctx.actor_id is None:
            return None
        state = getattr(self._core, "actor_state", None)
        return (state(ctx.actor_id) or {}).get("name") if state else None

    def get_worker_id(self) -> str:
        return getattr(self._core, "worker_id", self._core.node_id).hex()

    def get_assigned_resources(self) -> Dict[str, float]:
        getter = getattr(self._core, "assigned_resources", None)
        return getter() if getter else {}

    def get_accelerator_ids(self) -> Dict[str, List[str]]:
        """Host chip indices held by the running actor, as
        ``{"TPU": ["2"]}`` (reference: ``get_accelerator_ids``). The
        in-process runtime hands chips out per actor; a cluster worker
        was started with only its own chips visible."""
        getter = getattr(self._core, "accelerator_ids", None)
        if getter is not None:
            return getter()
        visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
        return {"TPU": [c for c in visible.split(",") if c]}

    def get_placement_group_id(self) -> Optional[str]:
        getter = getattr(self._core, "current_placement_group_id", None)
        pg = getter() if getter else None
        return pg.hex() if pg else None

    def was_current_actor_reconstructed(self) -> bool:
        return False

    @property
    def namespace(self) -> str:
        return _worker.global_worker().namespace

    def get_runtime_env_string(self) -> str:
        return "{}"


_runtime_context = RuntimeContext()


def get_runtime_context() -> RuntimeContext:
    return _runtime_context
