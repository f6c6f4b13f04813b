"""Production mesh construction (MULTI-POD DRY-RUN spec).

Defined as functions — importing this module never touches JAX device
state.  Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice);
multi-pod: (pod=2, data=16, model=16) = 512 chips, the ``pod`` axis being
an outer data-parallel axis (client groups / gradient all-reduce span it).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.compat import AxisType, make_mesh, make_mesh_exact
from repro.sharding.specs import AXIS_DATA, AXIS_MODEL, AXIS_POD


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ((AXIS_POD, AXIS_DATA, AXIS_MODEL) if multi_pod
            else (AXIS_DATA, AXIS_MODEL))
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, *, pods: int = 1):
    """Whatever this host (or, under ``jax.distributed``, this fleet)
    actually has (CPU tests / examples / the multi-process runtime).

    ``pods > 1`` produces the multi-pod layout ``("pod", "data", "model")``
    with the pod axis — the one whose collectives cross the DCN —
    outermost, exactly as in :func:`make_production_mesh`.  The device
    grid is laid out EXPLICITLY in ``(process, local)`` order so that pod
    row ``p`` is process ``p``'s devices when the fleet has one process
    per pod (``jax.make_mesh`` may permute devices for ring collectives,
    which would scatter a pod across processes); single-process runs get
    the same layout on forced host devices, so the 3-axis spec is
    exercised without a 512-chip fleet."""
    n = len(jax.devices())
    if model < 1 or pods < 1:
        raise ValueError(
            f"make_host_mesh: model={model} / pods={pods} must be >= 1")
    if n < model * pods:
        raise ValueError(
            f"make_host_mesh: {n} device(s) cannot host a "
            f"(pods={pods}, model={model}) mesh — need at least "
            f"{model * pods}; shrink --shard-model or force more host "
            "devices (XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    data = max(1, n // (model * pods))
    if pods > 1:
        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        grid = np.asarray(devs[: pods * data * model],
                          dtype=object).reshape(pods, data, model)
        return make_mesh_exact(grid, (AXIS_POD, AXIS_DATA, AXIS_MODEL))
    return make_mesh((data, model), (AXIS_DATA, AXIS_MODEL),
                     axis_types=(AxisType.Auto, AxisType.Auto))


def make_client_mesh(n_clients: int, model: int = 1):
    """Largest host mesh the client-sharded executor accepts for
    ``n_clients`` active clients: the data axis is the biggest device
    count that divides ``n_clients`` (the shard count must divide the
    client count).  1 device -> a degenerate (1, model) mesh, which still
    exercises the sharded program."""
    n = len(jax.devices())
    if model < 1 or n < model:
        raise ValueError(
            f"make_client_mesh: {n} device(s) cannot host model={model} "
            "model-parallel shards; shrink --shard-model or force more "
            "host devices")
    avail = max(1, n // model)
    data = max(d for d in range(1, avail + 1) if n_clients % d == 0)
    return make_mesh((data, model), (AXIS_DATA, AXIS_MODEL),
                     devices=jax.devices()[: data * model],
                     axis_types=(AxisType.Auto, AxisType.Auto))


def mesh_axes(mesh) -> tuple[tuple[str, ...], str]:
    """(data_axes, model_axis) for a mesh made by the functions above."""
    names = mesh.axis_names
    model_axis = AXIS_MODEL if AXIS_MODEL in names else names[-1]
    data_axes = tuple(n for n in names if n != model_axis)
    return data_axes, model_axis


def data_axes_size(mesh, data_axes=None) -> int:
    """Number of shards the client axis spreads over (product of the data
    axes' sizes — pod x data on a multi-pod mesh)."""
    if data_axes is None:
        data_axes, _ = mesh_axes(mesh)
    size = 1
    for a in data_axes:
        size *= mesh.shape[a]
    return size
