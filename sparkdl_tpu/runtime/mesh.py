"""Device-mesh discovery and construction.

The reference has no device mesh: its inference parallelism is one TF
session per Spark executor and its training parallelism is a Horovod ring
(SURVEY.md 2.11/2.13). The TPU-native equivalent is a named
``jax.sharding.Mesh`` over which pjit/shard_map place collectives on ICI.
This module owns mesh axis conventions for the whole framework:

  axis name | meaning
  ----------+----------------------------------------------
  ``dp``    | data parallel (batch split; psum of grads)
  ``fsdp``  | fully-sharded data parallel (param shard over dp peers)
  ``tp``    | tensor parallel (weight-column/row split)
  ``sp``    | sequence/context parallel (ring attention)
  ``pp``    | pipeline parallel (stage split)
  ``ep``    | expert parallel (MoE expert split)

Every model/transform in the framework refers to these names, never to raw
device indices.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Canonical axis ordering. dp outermost (DCN-friendly), then pp, fsdp, sp,
#: tp/ep innermost (highest-bandwidth ICI neighbours).
AXIS_ORDER = ("dp", "pp", "fsdp", "sp", "tp", "ep")


class MeshShapeError(ValueError):
    """A requested parallelism layout cannot be laid over the available
    devices (non-divisor axis sizes, duplicate axis names, bad product).

    Raised at mesh-construction time with the device count in the message
    — the alternative is an opaque reshape/jit error long after the bad
    shape was chosen (partition/mesh_factory.py is the loud front door)."""


def resolve_axis_sizes(sizes: "dict[str, int]", n_devices: int) -> dict[str, int]:
    """Resolve an ordered ``{axis: size}`` layout against ``n_devices``:
    at most one ``-1`` axis is inferred, everything else validated with a
    typed :class:`MeshShapeError` naming the device count. The one
    implementation behind :meth:`MeshSpec.resolve` and
    ``partition.mesh_factory``'s custom-axes builder."""
    sizes = dict(sizes)
    unknown = [a for a, s in sizes.items() if s == -1]
    if len(unknown) > 1:
        raise MeshShapeError(
            f"more than one -1 axis to infer: {unknown}"
        )
    bad = {a: s for a, s in sizes.items() if s != -1 and s < 1}
    if bad:
        raise MeshShapeError(
            f"mesh axis sizes must be >= 1 (or one -1 to infer), got "
            f"{bad} over {n_devices} devices"
        )
    known = math.prod(s for s in sizes.values() if s != -1)
    if unknown:
        if n_devices % known != 0:
            raise MeshShapeError(
                f"{n_devices} devices not divisible by the fixed axes "
                f"product {known} "
                f"({ {a: s for a, s in sizes.items() if s not in (1, -1)} })"
            )
        sizes[unknown[0]] = n_devices // known
    elif known != n_devices:
        raise MeshShapeError(
            f"mesh axes product {known} "
            f"({ {a: s for a, s in sizes.items() if s != 1} }) != "
            f"device count {n_devices}"
        )
    return sizes


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout, independent of physical device count.

    A size of 1 means the axis is inert (present in the mesh so that
    PartitionSpecs mentioning it always resolve, but no actual splitting).
    Sizes of -1 (at most one) are inferred from the device count.
    """

    dp: int = -1
    pp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    def sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> dict[str, int]:
        """Fill in the single -1 axis from n_devices; validate the product."""
        return resolve_axis_sizes(self.sizes(), n_devices)

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        if devices is None:
            devices = jax.devices()
        sizes = self.resolve(len(devices))
        shape = tuple(sizes[a] for a in AXIS_ORDER)
        arr = np.asarray(devices, dtype=object).reshape(shape)
        return Mesh(arr, AXIS_ORDER)


def data_parallel_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """All devices on the ``dp`` axis — the reference-parity layout

    (its only parallelism is DP; SURVEY.md 2.11)."""
    return MeshSpec(dp=-1).build(devices)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    if device is None:
        device = jax.devices()[0]
    return MeshSpec(dp=1).build([device])


def batch_sharding(mesh: Mesh, batch_axes: Sequence[str] = ("dp", "fsdp")) -> NamedSharding:
    """Sharding that splits the leading (batch) dim over the data axes."""
    return NamedSharding(mesh, P(tuple(batch_axes)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def shard_device_ids(tree) -> "list[int]":
    """Sorted ids of the devices the addressable shards of ``tree``'s
    arrays live on — what a multi-chip check asserts the size of (code
    that has only seen one device may put everything on the first)."""
    return sorted({s.device.id for leaf in jax.tree.leaves(tree)
                   for s in leaf.addressable_shards})

