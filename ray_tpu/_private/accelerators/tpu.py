"""TPU accelerator manager: detection, visibility, and slice topology labels.

Re-design of the reference TPU accelerator support (reference:
``python/ray/_private/accelerators/tpu.py:70`` — ``TPUAcceleratorManager``:
GCE metadata/env detection :47-118, ``TPU`` + per-pod ``TPU-<type>-head``
resources :330, ``TPU_VISIBLE_CHIPS`` :154, pod-type → accelerator-type
mapping :307). Here TPU chips are *the* first-class accelerator: the
scheduler accounts individual chips, and slice topology (ICI neighborhoods)
is exposed as ``TPU-slice:<name>`` resources so placement groups can request
ICI-connected chips.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
NUM_CHIPS_OVERRIDE_ENV = "RAY_TPU_NUM_CHIPS"
ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-256"
WORKER_ID_ENV = "TPU_WORKER_ID"

# Generations whose accelerator-type suffix counts CHIPS (one core per
# chip) vs TensorCores (two per chip). A host (TPU VM) holds up to 8 chips
# of the former when the whole slice fits one host, else 4; always up to
# 4 of the latter.
_SUFFIX_COUNTS_CHIPS = ("v5litepod", "v6e")
_SUFFIX_COUNTS_CORES = ("v2", "v3", "v4", "v5p")


def _chip_device_files() -> List[str]:
    """The chips the kernel exposes to this host: one ``/dev/accel<N>``
    each under the accel driver, one numbered ``/dev/vfio/<N>`` group
    each under VFIO. Looking costs nothing and holds nothing — unlike
    initialising a JAX backend, which takes every chip for this process."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return accel
    return [p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]


def _chips_per_host(acc_type: str) -> int:
    m = re.fullmatch(r"([a-z0-9]+)-(\d+)", acc_type.strip().lower())
    if m is None:
        raise ValueError(
            f"cannot parse {ACCELERATOR_TYPE_ENV}={acc_type!r} "
            "(expected <generation>-<count>, e.g. v5litepod-4)")
    gen, count = m.group(1), int(m.group(2))
    if gen in _SUFFIX_COUNTS_CHIPS:
        return count if count <= 8 else 4
    if gen in _SUFFIX_COUNTS_CORES:
        return min(max(count // 2, 1), 4)
    raise ValueError(
        f"unknown TPU generation {gen!r} in {ACCELERATOR_TYPE_ENV}="
        f"{acc_type!r}; set {NUM_CHIPS_OVERRIDE_ENV} to the host's chip "
        "count")


class TPUAcceleratorManager:
    """Static helpers; mirrors the reference AcceleratorManager ABC surface
    (``_private/accelerators/accelerator.py:5``)."""

    resource_name = "TPU"

    @staticmethod
    def detect_num_chips() -> int:
        """Number of TPU chips visible to this host. Never touches JAX:
        a process that initialises the backend to count chips owns them
        all, and the workers it then starts find none."""
        override = os.environ.get(NUM_CHIPS_OVERRIDE_ENV)
        if override is not None:
            return int(override)
        visible = os.environ.get(VISIBLE_CHIPS_ENV)
        if visible:
            return len([c for c in visible.split(",") if c != ""])
        files = _chip_device_files()
        if files:
            return len(files)
        acc_type = os.environ.get(ACCELERATOR_TYPE_ENV)
        if acc_type:
            return _chips_per_host(acc_type)
        return 0

    @staticmethod
    def accelerator_type() -> Optional[str]:
        return os.environ.get(ACCELERATOR_TYPE_ENV)

    @staticmethod
    def pod_name() -> Optional[str]:
        """Logical slice/pod name this host belongs to (for TPU-<pod>-head)."""
        return os.environ.get("TPU_NAME") or os.environ.get("TPU_POD_NAME")

    @staticmethod
    def worker_id() -> int:
        return int(os.environ.get(WORKER_ID_ENV, "0"))

    @staticmethod
    def set_visible_chips(chip_ids: List[int]) -> None:
        """Restrict this process (and its jax) to the given chips — the analog
        of CUDA_VISIBLE_DEVICES sharing in the reference
        (``worker.py:991``, ``backend_executor.py:278``)."""
        os.environ[VISIBLE_CHIPS_ENV] = ",".join(str(c) for c in chip_ids)
        # jax reads TPU_VISIBLE_CHIPS via libtpu at first init.

    @staticmethod
    def jax_device(chip: int):
        """The JAX device that is host chip ``chip`` in THIS process: a
        worker started with ``TPU_VISIBLE_CHIPS`` sees only its own
        chips, renumbered from 0; a process that sees the whole host
        (the in-process runtime) indexes them directly."""
        import jax

        visible = os.environ.get(VISIBLE_CHIPS_ENV)
        if visible:
            chip = [int(c) for c in visible.split(",") if c != ""].index(chip)
        return jax.local_devices()[chip]

    @staticmethod
    def node_resources() -> Dict[str, float]:
        """Resources this host contributes to the cluster."""
        n = TPUAcceleratorManager.detect_num_chips()
        if n == 0:
            return {}
        res: Dict[str, float] = {"TPU": float(n)}
        acc = TPUAcceleratorManager.accelerator_type()
        if acc:
            res[f"accelerator_type:{acc}"] = 1.0
            # The host with worker id 0 of a slice carries the slice-head
            # resource so exactly one actor per slice can claim coordination
            # (reference: TPU-<pod>-head resource, tpu.py:330).
            if TPUAcceleratorManager.worker_id() == 0:
                res[f"TPU-{acc}-head"] = 1.0
        pod = TPUAcceleratorManager.pod_name()
        if pod:
            res[f"TPU-slice:{pod}"] = float(n)
        return res
