"""Mesh construction for the production pod(s) and local test meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run forces 512 host devices *before*
any jax import; tests and benches see the default single device.

Axis convention (DESIGN.md §5):
  single-pod : (16, 16)    over ("data", "model")            — 256 chips
  multi-pod  : (2, 16, 16) over ("pod", "data", "model")     — 512 chips

The DataFrame engine row-shards tables over the data axes (("pod","data") in
multi-pod — flattened shared-nothing partitions); models do FSDP over the
data axes and tensor/expert parallelism over "model".
"""
from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices) -> Mesh:
    # Auto axes: the engine and the models place data with NamedSharding and
    # shard_map themselves; jax.make_mesh would otherwise build Explicit axes.
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"production mesh needs {ndev} devices, found {len(devices)}; "
            "the dry-run launcher must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax"
        )
    return _auto_mesh(shape, axes, devices[:ndev])


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A mesh over the first ``data * model`` local devices (one chip's
    sessions, a four-chip host, or forced CPU host devices in tests)."""
    ndev = data * model
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(f"need {ndev} devices, have {len(devices)}")
    return _auto_mesh((data, model), ("data", "model"), devices[:ndev])


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Names of the mesh axes a program should shard over.

    ``data`` may be a multi-axis tuple (("pod","data") on the multi-pod mesh) —
    every data-parallel sharding spec uses the tuple so the pod axis simply
    joins the FSDP/row-partition dimension.
    """

    data: tuple[str, ...] = ("data",)
    model: str = "model"

    @staticmethod
    def for_mesh(mesh: Mesh) -> "MeshAxes":
        names = mesh.axis_names
        if "pod" in names:
            return MeshAxes(data=("pod", "data"), model="model")
        if "model" in names:
            return MeshAxes(data=("data",), model="model")
        return MeshAxes(data=tuple(names), model=names[-1])

    def data_size(self, mesh: Mesh) -> int:
        return math.prod(mesh.shape[a] for a in self.data)

    def model_size(self, mesh: Mesh) -> int:
        return mesh.shape[self.model] if self.model in mesh.shape else 1
