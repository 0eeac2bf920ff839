"""Device-mesh construction — the topology half of the data plane.

The reference's topology artifact is the hostfile (`<host> slots=<n>` lines,
reference pkg/controllers/mpi_job_controller.go:857-869) consumed by mpirun.
The TPU-native artifact is a `jax.sharding.Mesh`: named axes over the device
array, onto which pjit/shard_map lay out shardings and XLA inserts
collectives over ICI (intra-slice) and DCN (inter-slice).

Axis vocabulary (scaling-book conventions):
  dp    — data parallel (batch dimension; gradient allreduce)
  fsdp  — fully-sharded data parallel (params sharded over the batch axis)
  tp    — tensor/model parallel (contracting-dim sharding; rides ICI)
  sp    — sequence/context parallel (ring attention; rides ICI neighbors)
  ep    — expert parallel (MoE all-to-all)
  pp    — pipeline parallel (stage-sharded layers; neighbor ppermute traffic)
  dcn   — the inter-slice axis for multi-slice jobs (data parallel over DCN,
          hierarchical allreduce for free from GSPMD)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order: outermost (slowest-varying, cross-slice first).
# pp sits outside dp: pipeline traffic is thin neighbor ppermute, so it can
# afford the outer (slower-link) placement; tp stays innermost on the
# fastest ICI links.
AXIS_ORDER = ("dcn", "pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclass
class MeshConfig:
    """Sizes for each mesh axis; 1 means the axis is collapsed (absent from
    sharding concerns but kept in the mesh for uniform PartitionSpecs)."""
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1       # pipeline stages
    dcn: int = 1      # number of slices (multi-slice data parallelism)

    def axis_sizes(self) -> Dict[str, int]:
        return {"dcn": self.dcn, "pp": self.pp, "dp": self.dp,
                "fsdp": self.fsdp, "ep": self.ep, "sp": self.sp,
                "tp": self.tp}

    @property
    def num_devices(self) -> int:
        return math.prod(self.axis_sizes().values())

    @staticmethod
    def data_parallel(n_devices: int, num_slices: int = 1) -> "MeshConfig":
        """The reference's sole strategy (SURVEY §2.3): pure DP allreduce.
        Multi-slice jobs put the slice count on the dcn axis."""
        if n_devices % num_slices != 0:
            raise ValueError(
                f"{n_devices} devices not divisible into {num_slices} slices")
        return MeshConfig(dp=n_devices // num_slices, dcn=num_slices)


def make_mesh(config: MeshConfig,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with the canonical axis order.

    For multi-slice (dcn > 1) on real hardware, mesh_utils'
    hybrid mesh keeps the dcn axis on the slow (DCN) links and the
    remaining axes on ICI; on a flat device set (CPU simulation, single
    slice) a plain reshape preserves ICI-neighbor adjacency for the
    innermost axes — tp innermost so its collectives ride the fastest
    links (SURVEY §7: lay out shardings so collectives ride ICI, not DCN).
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.axis_sizes()
    if config.num_devices != len(devices):
        raise ValueError(
            f"mesh asks for {config.num_devices} devices "
            f"({sizes}), got {len(devices)}")
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if config.dcn > 1 and devices[0].platform == "tpu":
        ici_shape = tuple(sizes[a] for a in AXIS_ORDER if a != "dcn")
        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=ici_shape,
            dcn_mesh_shape=(config.dcn,) + (1,) * (len(ici_shape) - 1),
            devices=devices,
        ).reshape(shape)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except (ValueError, AssertionError) as exc:
            if devices[0].platform == "tpu":
                # enumeration order is topology-blind: collectives would
                # cross non-neighbour ICI links and every timing taken on
                # that mesh would be of a layout nobody meant to run
                raise ValueError(
                    f"mesh_utils.create_device_mesh cannot lay "
                    f"{dict(sizes)} over {len(devices)} "
                    f"{devices[0].device_kind} devices: {exc}") from exc
            # virtual CPU devices have no topology to respect
            dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

#: batch dims shard over every data-like axis (dcn slices × dp × fsdp)
BATCH_AXES = ("dcn", "dp", "fsdp")


def batch_spec(extra: Tuple = ()) -> P:
    """PartitionSpec for a [batch, ...] array: batch over all data axes."""
    return P(BATCH_AXES, *extra)


def replicated_spec() -> P:
    return P()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    n = math.prod(mesh.shape[a] for a in BATCH_AXES)
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"data-parallel degree {n}")
    return global_batch // n


__all__ = [
    "AXIS_ORDER", "BATCH_AXES", "MeshConfig", "make_mesh",
    "batch_spec", "replicated_spec", "batch_sharding", "replicated_sharding",
    "local_batch_size", "Mesh", "NamedSharding", "P",
]
