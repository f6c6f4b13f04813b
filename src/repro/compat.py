"""The JAX API surface whose names have moved between JAX releases.

The repo supports the installed JAX (0.9).  The symbols below are the
ones whose home or spelling has drifted across releases (``shard_map``,
``make_mesh``/``AxisType``, the mesh context, the Pallas TPU surface);
every other module imports them from here, never from JAX directly
(reprolint RL001), so the next drift is absorbed in this one file.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

__all__ = [
    "AxisType", "axis_index", "cost_analysis", "is_tpu", "make_mesh",
    "make_mesh_exact", "pallas_compiler_params", "pl", "pltpu", "shard_map",
    "use_mesh",
]


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` (which may order devices for ring collectives)."""
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         devices=devices)


def make_mesh_exact(device_grid, axis_names):
    """``jax.sharding.Mesh`` with the EXACT device order of ``device_grid``
    (an ndarray of devices already shaped like the mesh).

    ``jax.make_mesh`` may permute devices for ring-efficient collectives;
    multi-pod meshes must NOT be permuted — the pod axis has to stay the
    process axis or a pod's shards land behind another process's memory.
    Every axis is Auto, the only axis type this repo uses."""
    from jax.sharding import Mesh
    return Mesh(device_grid, axis_names)


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None) -> Callable:
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


@contextlib.contextmanager
def use_mesh(mesh):
    """Enter ``mesh`` as the ambient mesh (``jax.set_mesh``)."""
    with jax.set_mesh(mesh):
        yield mesh


def axis_index(axis_names) -> Any:
    """Flattened index of this shard over one or more mapped mesh axes,
    row-major, so it matches the block order of a leading array axis
    sharded with ``PartitionSpec(("pod", "data"), ...)``."""
    return jax.lax.axis_index(axis_names)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_compiler_params(**kwargs):
    """The Pallas TPU compiler-params struct (``pltpu.CompilerParams``)."""
    return pltpu.CompilerParams(**kwargs)
