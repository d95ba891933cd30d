"""What every Pallas dispatcher under ``ops/`` asks before it builds a
kernel: whether to run it interpreted, and how a tri-state environment
switch reads."""

from __future__ import annotations

import os
from typing import Optional

import jax


def env_flag(name: str) -> Optional[bool]:
    """Tri-state env knob: '1'/'true'/'on' -> True, '0'/'false'/'off' ->
    False, unset/other -> None (auto)."""
    val = os.environ.get(name, "").strip().lower()
    if val in ("1", "true", "on", "yes"):
        return True
    if val in ("0", "false", "off", "no"):
        return False
    return None


def interpret_default() -> bool:
    """Interpret mode unless on a TPU; ``RAY_TPU_PALLAS_INTERPRET``
    forces either way (the ``pallas_interpret`` conftest fixture, and
    ``=0`` for a chipless TPU compile)."""
    forced = env_flag("RAY_TPU_PALLAS_INTERPRET")
    if forced is not None:
        return forced
    return jax.default_backend() != "tpu"
