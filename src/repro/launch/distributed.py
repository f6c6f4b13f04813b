"""Multi-process (multi-pod) runtime bootstrap for the sharded executor.

One OS process per pod: ``initialize`` wires this process into a
``jax.distributed`` fleet (coordinator discovery via ``REPRO_*`` env or
explicit arguments), after which ``jax.devices()`` spans every pod and
``repro.launch.mesh.make_host_mesh(pods=jax.process_count())`` lays the
``("pod", "data", "model")`` mesh out with the pod axis — the
DCN-crossing axis — outermost and aligned with process boundaries, so
the Eq. (7) psum and the memory-queue all-gather are the only traffic
that rides the cross-pod links.

Data stays per-pod: each process constructs loaders (and one prefetch
worker) only for its own client block and contributes its
``(K, n_local, B, ...)`` slab to the global batch via
``jax.make_array_from_process_local_data`` (:func:`make_pod_array`) —
no host ever materializes another pod's samples.  Replicated values
(supervised stacks, carried server state) are placed with
:func:`put_replicated`; host-side reads of replicated outputs go
through :func:`fetch`, which every process performs identically so the
adaptation controller and the client-selection RNG stay in lockstep
without any extra synchronization.

On CPU fleets (CI, the localhost repro command in the README) the
cross-process collectives need jaxlib's Gloo TCP backend, which must be
selected *before* the CPU client exists — ``initialize`` does this via
``jax.config`` (the knob is ignored by accelerator backends).
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_CPU_COLLECTIVES = "REPRO_CPU_COLLECTIVES"

DEFAULT_COORDINATOR = "127.0.0.1:12321"


@dataclass(frozen=True)
class DistInfo:
    """What :func:`initialize` resolved: the fleet shape and whether this
    process actually joined one (``num_processes == 1`` is the no-op
    single-process path — nothing was initialized and nothing needs
    shutting down)."""

    num_processes: int
    process_id: int
    coordinator: Optional[str]

    @property
    def active(self) -> bool:
        return self.num_processes > 1

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


_INITIALIZED: Optional[DistInfo] = None


def _env_int(env: dict, name: str) -> Optional[int]:
    v = env.get(name)
    if v is None or v == "":
        return None
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}") from None


def enable_cpu_collectives(impl: Optional[str] = None) -> Optional[str]:
    """Select the CPU cross-process collectives backend (default: gloo).

    Must run before the CPU client is created — jaxlib builds the client
    with or without a collectives implementation once.  The env knob is
    ``REPRO_CPU_COLLECTIVES`` (``gloo`` | ``mpi`` | ``none``).  Returns
    the implementation selected, or None when it was explicitly
    disabled."""
    import jax

    impl = impl or os.environ.get(ENV_CPU_COLLECTIVES, "gloo")
    if impl in ("none", "off", ""):
        return None
    # a ValueError (explicitly requested but invalid value) propagates:
    # silently degrading to no collectives backend would surface as an
    # opaque hang/crash at the first cross-process psum instead
    jax.config.update("jax_cpu_collectives_implementation", impl)
    return impl


def initialize(num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               coordinator: Optional[str] = None, *,
               env: Optional[dict] = None,
               timeout_s: int = 300) -> DistInfo:
    """Join (or skip joining) a ``jax.distributed`` fleet.

    Arguments win over the ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
    / ``REPRO_COORDINATOR`` environment.  ``num_processes`` absent or
    ``<= 1`` is the single-process no-op.  Idempotent: a second call with
    the same topology returns the original info; a different topology is
    an error (jax.distributed cannot be re-initialized)."""
    global _INITIALIZED
    e = os.environ if env is None else env
    if num_processes is None:
        num_processes = _env_int(e, ENV_NUM_PROCESSES)
    if process_id is None:
        process_id = _env_int(e, ENV_PROCESS_ID)
    if coordinator is None:
        coordinator = e.get(ENV_COORDINATOR) or None

    if num_processes is None or num_processes <= 1:
        # the single-process no-op: nothing is initialized, so it must
        # neither conflict with a live fleet nor block a later genuine
        # fleet join in the same process
        if _INITIALIZED is not None and _INITIALIZED.active:
            raise RuntimeError(
                f"jax.distributed already initialized as {_INITIALIZED}; "
                "cannot drop back to single-process in the same process")
        info = DistInfo(1, 0, None)
        _INITIALIZED = info
        return info

    if process_id is None:
        raise ValueError(
            f"multi-process run ({num_processes} processes) needs a process "
            f"id: set {ENV_PROCESS_ID} (the local spawner does) or pass "
            "--process-id")
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} out of range for "
            f"{num_processes} processes")
    coordinator = coordinator or DEFAULT_COORDINATOR

    info = DistInfo(num_processes, process_id, coordinator)
    if _INITIALIZED is not None and _INITIALIZED.active:
        if _INITIALIZED == info:
            return info
        raise RuntimeError(
            f"jax.distributed already initialized as {_INITIALIZED}, "
            f"refusing to re-initialize as {info}")

    import jax
    enable_cpu_collectives()
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id,
                               initialization_timeout=timeout_s)
    _INITIALIZED = info
    return info


def shutdown() -> None:
    """Leave the fleet (no-op when :func:`initialize` was the
    single-process path or never ran)."""
    global _INITIALIZED
    if _INITIALIZED is not None and _INITIALIZED.active:
        import jax
        jax.distributed.shutdown()
    _INITIALIZED = None


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def is_coordinator() -> bool:
    return process_index() == 0


# ---------------------------------------------------------------------------
# mesh <-> process topology
# ---------------------------------------------------------------------------

def pod_index(mesh) -> int:
    """This process's pod row in ``mesh``, verifying the pod axis is the
    process axis: with P processes the mesh must have a leading ``pod``
    axis of size P whose row p consists entirely of process p's devices
    (the DCN-friendly layout ``make_host_mesh(pods=P)`` builds).  Any
    other arrangement would put a pod's client shards behind another
    process's memory, so it is rejected loudly."""
    import jax

    procs = jax.process_count()
    if procs == 1:
        return 0
    names = mesh.axis_names
    if "pod" not in names or names[0] != "pod":
        raise ValueError(
            f"multi-process mesh needs a leading 'pod' axis, got axes "
            f"{names} (use make_host_mesh(pods=jax.process_count()))")
    n_pods = mesh.shape["pod"]
    if n_pods != procs:
        raise ValueError(
            f"mesh pod axis has size {n_pods} but there are {procs} "
            "processes; one pod per process is required")
    devs = np.asarray(mesh.devices)
    for p in range(n_pods):
        owners = {d.process_index for d in devs[p].ravel()}
        if owners != {p}:
            raise ValueError(
                f"pod row {p} spans processes {sorted(owners)}; each pod "
                "must be exactly one process's devices (device order "
                "drifted — rebuild the mesh with make_host_mesh)")
    return jax.process_index()


# ---------------------------------------------------------------------------
# host <-> global-array plumbing
# ---------------------------------------------------------------------------

def put_replicated(tree: Any, mesh) -> Any:
    """Place every leaf of ``tree`` fully replicated over ``mesh``.

    Each process supplies its own (identical, by the engine's lockstep
    construction) host value.  Deliberately NOT ``jax.device_put``: on a
    non-addressable sharding device_put runs ``multihost_utils
    .assert_equal`` — a hidden psum — per leaf, and a hidden collective
    is both slow and LETHAL from the prefetch worker thread (two threads
    per process launching collectives in nondeterministic relative order
    interleave the fleet's Gloo streams: ``op.preamble.length <=
    op.nbytes`` crashes).  ``make_array_from_process_local_data`` with
    the full value builds the local shards collective-free."""
    import jax

    from repro.sharding.specs import replicated_sharding

    def one(leaf):
        leaf = np.asarray(leaf)
        return jax.make_array_from_process_local_data(
            replicated_sharding(mesh, leaf.ndim), leaf, leaf.shape)

    return jax.tree.map(one, tree)


def put_from_full(tree: Any, shardings: Any) -> Any:
    """Commit host-identical full values onto arbitrary shardings.

    Every process holds the same full host value (the engine's lockstep
    construction); each materializes only its addressable shards by
    slicing that value per device index — no cross-process transfer, safe
    whatever the sharding (client axis over ``("pod", "data")``,
    model-parallel top parameters, replicated queue/metrics alike).  This
    is the state placement for the model-sharded LM phase, whose
    ``arg_shardings`` mix all three."""
    import jax

    def one(leaf, sh):
        leaf = np.asarray(leaf)
        return jax.make_array_from_callback(leaf.shape, sh,
                                            lambda idx: leaf[idx])

    return jax.tree.map(one, tree, shardings)


def make_pod_array(sharding, local: np.ndarray,
                   global_shape: tuple) -> Any:
    """Assemble a global array from this process's slab.

    ``sharding`` names which mesh axes each dim spreads over; ``local``
    is the block this process owns (its addressable portion, e.g. the
    ``(K, n_local, B, ...)`` client slab of a ``(K, N, B, ...)`` stack
    whose client axis is sharded over ``("pod", "data")``).  Thin wrapper
    over ``jax.make_array_from_process_local_data`` so call sites don't
    repeat the shape bookkeeping."""
    import jax

    return jax.make_array_from_process_local_data(sharding,
                                                  np.ascontiguousarray(local),
                                                  global_shape)


def fetch(x: Any) -> np.ndarray:
    """Host value of ``x`` even when it spans other processes' devices.

    Multi-process program outputs that are replicated (the engine pins
    its metric/state outputs that way) carry a full copy in every
    process's addressable shards but refuse plain ``np.asarray``; this
    reads the local copy.  Every process gets the same bytes, so code
    paths keyed on fetched values (the Eq. (10) controller, client
    selection) stay in lockstep for free."""
    import jax

    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        if not x.is_fully_replicated:
            raise ValueError(
                "fetch() on a non-replicated multi-process array; "
                "all-gather it in-program or read .addressable_shards")
        return np.asarray(x.addressable_shards[0].data)
    return np.asarray(x)


def fetch_tree(tree: Any) -> Any:
    """:func:`fetch` over a pytree (checkpoint writes on process 0)."""
    import jax

    return jax.tree.map(fetch, tree)


# ---------------------------------------------------------------------------
# localhost spawner (CI-identical repro command)
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local(num_processes: int, argv: Optional[Sequence[str]] = None, *,
                coordinator: Optional[str] = None,
                env_extra: Optional[dict] = None) -> int:
    """Re-exec this program ``num_processes`` times with the ``REPRO_*``
    fleet env set (one child per pod, all on this host), stream their
    output, and return the first nonzero exit code (0 if all clean).

    ``python -m repro.launch.train --num-processes 2 ...`` uses this when
    no process id is set: the parent only spawns and waits — children see
    ``REPRO_PROCESS_ID`` and take the initialize path.

    The fleet is a CPU rig: it starts only when the children's
    environment pins ``JAX_PLATFORMS=cpu``.  On an accelerator host each
    child would claim every chip; there one process drives all of the
    host's chips (``--shard-clients``)."""
    import time

    env = dict(os.environ, **(env_extra or {}))
    if env.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "a localhost fleet runs on CPU only: set JAX_PLATFORMS=cpu.  "
            "On a TPU host one process drives every chip; run a single "
            "process with --shard-clients instead of --num-processes")
    argv = list(sys.argv if argv is None else argv)
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_COORDINATOR] = coordinator
    procs = []
    for p in range(num_processes):
        child_env = dict(env, **{ENV_PROCESS_ID: str(p)})
        procs.append(subprocess.Popen([sys.executable] + argv,
                                      env=child_env))
    # one dead pod deadlocks its peers in their next collective, so a
    # child failure tears the rest of the fleet down (grace period for
    # jax.distributed's own error propagation first) instead of hanging
    # the parent forever
    rc = 0
    alive = dict(enumerate(procs))
    while alive and not rc:
        for p, proc in list(alive.items()):
            code = proc.poll()
            if code is not None:
                del alive[p]
                if code and not rc:
                    rc = code
        time.sleep(0.2)
    if alive and rc:
        deadline = time.time() + 30.0
        while alive and time.time() < deadline:
            for p, proc in list(alive.items()):
                if proc.poll() is not None:
                    del alive[p]
            time.sleep(0.2)
        for p, proc in alive.items():
            print(f"spawn_local: terminating pod {p} (peer failed with "
                  f"rc={rc})", file=sys.stderr, flush=True)
            proc.terminate()
            try:
                proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    return rc
