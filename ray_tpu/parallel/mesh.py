"""Device-mesh construction and canonical sharding axes.

Canonical mesh axes (outermost to innermost, i.e. DCN-most to ICI-most):

  pipeline — pipeline parallelism; layer stacks sharded by stage, microbatch
            activations handed off with `ppermute` (parallel/pipeline.py).
            Outermost: stage handoffs are point-to-point and latency-tolerant,
            so they ride DCN across slices (SURVEY §5 item (b)).
  data    — pure data parallelism; gradients all-reduced. Crosses slices
            (DCN) in multi-slice deployments.
  fsdp    — data parallelism with parameters/optimizer sharded over the axis
            (XLA inserts per-layer all-gathers / reduce-scatters).
  expert  — expert parallelism for MoE layers; token dispatch/combine
            lowers to XLA all-to-alls over this axis (ray_tpu.models.moe).
  context — sequence (context) parallelism; ring attention rides neighbour
            ICI links (ray_tpu.ops.ring_attention).
  tensor  — megatron-style tensor parallelism; highest-traffic axis, mapped
            to the innermost ICI dimension.

Axis order in the mesh tuple encodes the physical hierarchy: `jax.make_mesh`
lays later axes on nearer devices.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("pipeline", "data", "fsdp", "expert", "context", "tensor")

# batch dims of activations/token arrays are sharded over both DP axes
BATCH_AXES = ("data", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism degrees. Product must equal the device count.

    ``num_slices > 1`` builds a hybrid ICI×DCN mesh: devices are grouped
    into slices (TPU ICI domains) and the ``data`` axis is laid out with
    slices outermost, so ONLY data-parallel gradient reduction crosses the
    slow DCN links while fsdp/expert/context/tensor collectives stay on
    intra-slice ICI (SURVEY §5 item (b); reference slice machinery:
    python/ray/_private/accelerators/tpu.py:316-334).
    """

    data: int = 1
    fsdp: int = 1
    expert: int = 1
    context: int = 1
    tensor: int = 1
    pipeline: int = 1
    # DCN data-parallel granules; `data` must be a multiple of it. With
    # pipeline > 1 the total slice count is pipeline * num_slices (stages
    # are DCN-level too — handoffs are p2p and latency-tolerant).
    num_slices: int = 1

    @property
    def num_devices(self) -> int:
        return (self.pipeline * self.data * self.fsdp * self.expert
                * self.context * self.tensor)

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        if devices is None:
            devices = jax.devices()
        shape = (self.pipeline, self.data, self.fsdp, self.expert,
                 self.context, self.tensor)
        if math.prod(shape) != len(devices):
            raise ValueError(
                f"mesh {shape} needs {math.prod(shape)} devices, have {len(devices)}"
            )
        if self.data % self.num_slices:
            raise ValueError(
                f"data={self.data} must be a multiple of num_slices="
                f"{self.num_slices}: DCN-crossing parallelism is data-parallel "
                f"over slices (fsdp/context/tensor must stay on ICI)")
        # hybrid (slice-aware) layout whenever an axis is declared DCN-level
        # AND the devices actually span multiple granules; a single-process
        # CPU/test mesh takes the plain path (there is no DCN to align to)
        granules = {(getattr(d, "slice_index", None), d.process_index)
                    for d in devices}
        if (self.num_slices > 1 or self.pipeline > 1) and len(granules) > 1:
            return self._build_hybrid(devices, shape)
        # Auto axis types: shardings flow via with_sharding_constraint +
        # XLA propagation (make_mesh would default new meshes to Explicit)
        auto = (jax.sharding.AxisType.Auto,) * len(MESH_AXES)
        return jax.make_mesh(shape, MESH_AXES, devices=devices, axis_types=auto)

    def _build_hybrid(self, devices: Sequence, shape) -> Mesh:
        """ICI×DCN mesh: per-slice shape × across-slice shape."""
        from jax.experimental import mesh_utils

        ici = (1, self.data // self.num_slices, self.fsdp, self.expert,
               self.context, self.tensor)
        dcn = (self.pipeline, self.num_slices, 1, 1, 1, 1)
        # real TPU slices carry distinguishing slice_index values; virtual/CPU
        # multi-process deployments (all slice_index 0 or absent) use the
        # process as the DCN granule instead
        n_granules = self.pipeline * self.num_slices
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        use_slice_index = len(slice_ids) == n_granules and None not in slice_ids
        arr = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices, process_is_granule=not use_slice_index)
        return Mesh(arr, MESH_AXES)

    @classmethod
    def for_devices(cls, n: int, *, tensor: int = 1, context: int = 1) -> "MeshSpec":
        """A sensible default: given n devices, put the remainder on fsdp."""
        rem, r = divmod(n, tensor * context)
        if r:
            raise ValueError(f"{n} devices not divisible by tensor*context={tensor * context}")
        return cls(data=1, fsdp=rem, context=context, tensor=tensor)


def batch_spec(*, context_sharded: bool = False) -> P:
    """PartitionSpec for [batch, seq, ...] arrays."""
    return P(BATCH_AXES, "context" if context_sharded else None)


def local_mesh(spec: Optional[MeshSpec] = None) -> Mesh:
    """Mesh over this process's local devices (single-host convenience)."""
    if spec is None:
        n = len(jax.local_devices())
        spec = MeshSpec.for_devices(n)
    return spec.build(jax.local_devices())


def shard_pytree(tree, spec_tree, mesh: Mesh):
    """Device-put a pytree according to a matching PartitionSpec tree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, spec_tree
    )
