"""Device-mesh construction and axis conventions for ray_tpu.

This is the TPU-native replacement for the reference's process-group world
(``torch.distributed`` bootstrapped by Ray Train — reference:
``python/ray/train/torch/config.py:153``): instead of ranks + NCCL
communicators, parallelism is expressed as a named :class:`jax.sharding.Mesh`
over the TPU slice, and every collective lowers to XLA ICI/DCN collectives.

Axis conventions (MaxText/t5x-style logical mesh):

===========  =============================================================
axis         meaning
===========  =============================================================
``data``     pure data parallelism (batch sharding, gradients psum)
``fsdp``     ZeRO-3-style parameter/optimizer sharding (also shards batch)
``tensor``   tensor (Megatron-style) model parallelism
``seq``      sequence/context parallelism (ring attention / Ulysses)
``expert``   expert parallelism for MoE dispatch
``stage``    pipeline stages
===========  =============================================================

A mesh does not need every axis: absent axes have size 1 and are dropped.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical mesh-axis order. ICI-heavy axes (tensor/seq) are placed last so
# they land on the innermost (fastest-wraparound, torus-adjacent) dimensions
# of the device array; DCN-friendly axes (data/stage) come first.
MESH_AXES: Tuple[str, ...] = ("stage", "data", "fsdp", "expert", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. ``-1`` on one axis means "all remaining devices"."""

    data: int = 1
    fsdp: int = -1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {
            "stage": self.stage,
            "data": self.data,
            "fsdp": self.fsdp,
            "expert": self.expert,
            "seq": self.seq,
            "tensor": self.tensor,
        }
        wildcard = [k for k, v in sizes.items() if v == -1]
        if len(wildcard) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wildcard}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wildcard:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} are available"
            )
        return sizes


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """Build a named Mesh over ``devices`` (default: all global devices).

    TPU devices are laid out by
    :func:`jax.experimental.mesh_utils.create_device_mesh`, so the logical
    mesh follows the physical ICI torus (nearest-neighbor collectives stay
    on-link). If that fails the error is raised: a reshape in enumeration
    order would silently decide which chips are neighbours. Other
    platforms, and a single device, have no topology to honour and are
    reshaped in the order given.
    """
    config = config or MeshConfig()
    devices = list(devices) if devices is not None else jax.devices()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    if devices[0].platform == "tpu" and len(devices) > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1,) * len(MESH_AXES)), MESH_AXES)


def mesh_shape(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh: Mesh) -> List[str]:
    """Mesh axes over which the global batch is sharded."""
    return [a for a in ("data", "fsdp") if mesh_shape(mesh).get(a, 1) > 1]


def num_model_replicas(mesh: Mesh) -> int:
    s = mesh_shape(mesh)
    return s.get("data", 1) * s.get("fsdp", 1)
