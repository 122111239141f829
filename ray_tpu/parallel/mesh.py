"""Device mesh construction for TPU slices.

The TPU-native analogue of the reference's process-group bootstrap
(train/torch/config.py:66-153 _setup_torch_process_group): instead of
`dist.init_process_group(nccl)`, parallelism is declared as a
`jax.sharding.Mesh` with named axes, and XLA inserts ICI/DCN collectives
from sharding annotations (scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert collectives).

Axis conventions used across the framework:
  * ``dp``   — data parallel (batch sharding; gradient psum)
  * ``fsdp`` — param/optimizer sharding (ZeRO-equivalent; psum_scatter)
  * ``tp``   — tensor parallel (Megatron partition of matmuls)
  * ``pp``   — pipeline stages
  * ``sp``   — sequence/context parallel (ring attention axis)
  * ``ep``   — expert parallel (MoE all_to_all axis)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")


@dataclass
class MeshConfig:
    """Declarative mesh shape. Unset axes default to 1. `dp=-1` means
    "absorb all remaining devices" (like the reference ScalingConfig's
    num_workers covering the worker group)."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        fixed = {"fsdp": self.fsdp, "tp": self.tp, "pp": self.pp,
                 "sp": self.sp, "ep": self.ep}
        known = int(np.prod(list(fixed.values())))
        dp = self.dp
        if dp == -1:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {known}")
            dp = n_devices // known
        total = dp * known
        if total != n_devices:
            raise ValueError(
                f"Mesh shape {dict(dp=dp, **fixed)} needs {total} devices, "
                f"have {n_devices}")
        return {"dp": dp, **fixed}


def best_mesh_shape(n_devices: int, model_parallel: int = 1
                    ) -> Tuple[int, int]:
    """(dp, tp) split for n devices given a model-parallel degree."""
    if n_devices % model_parallel != 0:
        raise ValueError(f"{n_devices} % {model_parallel} != 0")
    return n_devices // model_parallel, model_parallel


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None,
              axis_names: Optional[Sequence[str]] = None):
    """Build a Mesh with the framework's axis names.

    On real hardware, uses jax's device topology ordering
    (mesh_utils.create_device_mesh) so ICI neighbours land adjacent on the
    mesh, and a shape the topology cannot hold is an error. CPU devices
    have no topology: they are reshaped in order.
    """
    import jax
    from jax.sharding import Mesh

    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    shape_map = config.resolve(len(devices))
    names = tuple(axis_names or [a for a in AXIS_ORDER])
    shape = tuple(shape_map.get(a, 1) for a in names)
    if devices[0].platform == "cpu":
        dev_array = np.array(devices).reshape(shape)
    else:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=np.array(devices))
    return Mesh(dev_array, names)


def slice_count(devices: Optional[Sequence] = None) -> int:
    """Number of TPU slices in the runtime (multi-slice/megascale
    deployments expose `device.slice_index`; single-slice and CPU
    backends count as 1)."""
    import jax
    devices = list(devices if devices is not None else jax.devices())
    indices = {getattr(d, "slice_index", 0) for d in devices}
    return max(1, len(indices))


def make_multislice_mesh(config: Optional[MeshConfig] = None,
                         devices: Optional[Sequence] = None,
                         dcn_axis: str = "dp_dcn",
                         num_slices: Optional[int] = None):
    """Mesh spanning MULTIPLE pod slices: a leading data-parallel axis
    over DCN plus the usual ICI axes within each slice.

    The scaling-book multi-slice recipe: only data parallelism (gradient
    all-reduce once per step) crosses the slow DCN links; tensor/
    sequence/expert axes stay inside a slice on ICI. XLA's megascale
    path lowers collectives over the `dcn_axis` to DCN transfers
    automatically when the mesh is built with slice-aware device
    ordering (jax mesh_utils.create_hybrid_device_mesh).

    On CPU test backends (no slice_index), pass `num_slices` to emulate
    slices as contiguous device groups — the SURVEY §4 CPU-mirror
    pattern, exercised by tests/test_parallel.py and the driver dryrun.

    Reference contrast: the reference has no multi-slice story in-tree —
    its DCN-scale path is torch DDP over NCCL/EFA configured by users
    (train/torch/config.py); here the hybrid mesh IS the API.
    """
    import jax
    from jax.sharding import Mesh

    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n_slices = num_slices or slice_count(devices)
    if n_slices <= 1:
        raise ValueError(
            "make_multislice_mesh needs >1 slice (pass num_slices to "
            "emulate on test backends); use make_mesh for single-slice")
    if len(devices) % n_slices != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices")
    per_slice = len(devices) // n_slices
    ici_shape_map = config.resolve(per_slice)
    names = (dcn_axis,) + tuple(AXIS_ORDER)
    ici_shape = tuple(ici_shape_map.get(a, 1) for a in AXIS_ORDER)
    real_slices = all(hasattr(d, "slice_index") for d in devices)
    if real_slices:
        # Real multi-slice hardware: slice-aware ordering is mandatory —
        # a shape error here must SURFACE (a silent contiguous reshape
        # would cut dp_dcn groups across physical slices and route
        # in-slice collectives over DCN).
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, (n_slices,) + (1,) * len(AXIS_ORDER),
            devices=devices)
    else:
        # CPU/test backend: contiguous groups act as slices.
        dev_array = np.array(devices).reshape((n_slices,) + ici_shape)
    return Mesh(dev_array, names)


def make_1d_mesh(axis: str = "dp", devices: Optional[Sequence] = None):
    import jax
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (axis,))


def mesh_axis_size(mesh, axis: str) -> int:
    return int(mesh.shape.get(axis, 1))


def local_slice_info() -> Dict[str, object]:
    """Host's view of the slice (reference: tpu.py pod metadata —
    worker id, pod name, chips per host)."""
    import jax
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
    }
