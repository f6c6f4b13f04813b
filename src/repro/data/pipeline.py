"""Batching pipeline: labeled server loader + per-client unlabeled loaders.

Numpy-side sampling (cheap, CPU) feeding jnp arrays to jitted steps.  Each
loader is an infinite sampler with its own RandomState so experiments are
reproducible per seed.

Loaders implement a *restartable iterator protocol* —
:meth:`Loader.state_dict` / :meth:`Loader.load_state_dict` /
:meth:`Loader.clone` capture and restore the full sampling state (RNG +
current permutation + cursor).  The async prefetch worker
(``repro.data.prefetch``) relies on it: speculative draws for the next
round are rolled back when the engine's actual request differs (a K_s
adaptation round), so the prefetched and synchronous executors consume
bit-identical sample streams.
"""
from __future__ import annotations

import copy

import numpy as np

from repro.data.synthetic import Dataset


class Loader:
    """Infinite shuffled batch sampler over a (subset of a) dataset.

    Epoch semantics: samples are drawn from a seeded permutation of the
    index set; a batch that reaches the end of the permutation *finishes
    the epoch* and continues into a fresh permutation — no sample is
    dropped or repeated mid-epoch, whatever the partition size modulo
    batch (partitions smaller than a batch simply span several epochs per
    batch).  Every loader therefore wraps at exactly ``len(self)`` draws,
    so ragged client partitions recycle their samples at deterministic,
    per-loader epoch boundaries instead of drifting with the batch size.
    """

    def __init__(self, ds: Dataset, indices: np.ndarray | None, batch: int,
                 seed: int):
        self.ds = ds
        self.idx = np.arange(len(ds.y)) if indices is None else np.asarray(indices)
        if len(self.idx) == 0:
            raise ValueError("Loader needs a non-empty index set")
        self.batch = batch
        self.rng = np.random.RandomState(seed)
        self._order = self.rng.permutation(self.idx)
        self._cursor = 0

    def __len__(self):
        return len(self.idx)

    # -- restartable iterator protocol ---------------------------------
    def state_dict(self) -> dict:
        """Full sampling state; restoring it replays the exact stream."""
        return {"rng": self.rng.get_state(), "order": self._order.copy(),
                "cursor": self._cursor}

    def load_state_dict(self, sd: dict) -> None:
        self.rng.set_state(sd["rng"])
        self._order = sd["order"].copy()
        self._cursor = sd["cursor"]

    def clone(self) -> Loader:
        """Independent loader continuing this one's exact stream (shares
        the dataset arrays, deep-copies the sampling state)."""
        other = copy.copy(self)
        other.rng = np.random.RandomState()
        other.load_state_dict(self.state_dict())
        return other

    # -- sampling ------------------------------------------------------
    def _take(self, n: int) -> np.ndarray:
        take = np.empty(n, dtype=self.idx.dtype)
        filled = 0
        while filled < n:
            avail = len(self._order) - self._cursor
            if avail == 0:
                self._order = self.rng.permutation(self.idx)
                self._cursor = 0
                avail = len(self._order)
            m = min(n - filled, avail)
            take[filled: filled + m] = \
                self._order[self._cursor: self._cursor + m]
            self._cursor += m
            filled += m
        return take

    def next(self) -> tuple[np.ndarray, np.ndarray]:
        take = self._take(self.batch)
        return self.ds.x[take], self.ds.y[take]

    def next_many(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Prefetch ``k`` batches -> ``(K, B, ...)`` stacks for the
        scan-compiled phase executor.  Draws exactly the same sample
        sequence as ``k`` successive :meth:`next` calls, so the scanned
        phase and a per-step loop see identical data."""
        xs, ys = zip(*(self.next() for _ in range(k)))
        return np.stack(xs), np.stack(ys)


def client_loaders(ds: Dataset, parts: list[np.ndarray], batch: int,
                   seed: int, *, only: range | list[int] | None = None
                   ) -> list[Loader]:
    """One loader per client partition.  ``only`` restricts construction
    to those GLOBAL client ids (per-pod loading) while keeping every
    loader's seed keyed by its global id — client ``i``'s sample stream
    is identical whether it was built on one host or on its pod."""
    ids = range(len(parts)) if only is None else only
    return [Loader(ds, parts[i], batch, seed + 31 * i) for i in ids]


def stack_client_batches(loaders: list[Loader], active: list[int]):
    """Sample one batch per active client -> stacked (N, B, ...) arrays."""
    xs, ys = zip(*(loaders[i].next() for i in active))
    return np.stack(xs), np.stack(ys)


def stack_client_batches_many(loaders: list[Loader], active: list[int],
                              k: int, *, shardings=None
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Prefetch ``k`` rounds of client batches -> ``(K, N, B, ...)`` stacks
    for the scanned cross-entity phase.  Iteration-major draw order matches
    ``k`` successive :func:`stack_client_batches` calls exactly, and each
    client's ``(K, B, ...)`` slab wraps its partition at the loader's own
    deterministic epoch boundary (see :class:`Loader`) — a client whose
    partition is smaller than ``k * batch`` recycles samples at exactly
    ``len(loader)`` draws, in phase with successive single draws.

    With ``shardings=(x_sharding, y_sharding)`` (NamedShardings whose spec
    puts the client axis on the mesh's data axes) the stacks are
    ``device_put`` directly onto the mesh, so each client's ``(K, B, ...)``
    slab lands on its shard and the sharded phase executor starts without
    an extra host->replicated->resharded hop.  Either entry may instead be
    a *callable* ``stack -> device value`` — the multi-process engine
    passes the per-pod assembler that turns this process's local
    ``(K, n_local, B, ...)`` slab into the global client-sharded array
    (``jax.make_array_from_process_local_data``).  Either entry may be
    None to skip that transfer (the cross-entity phase never consumes the
    labels, so the engine passes ``(x_sharding, None)``)."""
    xs, ys = zip(*(stack_client_batches(loaders, active) for _ in range(k)))
    xs, ys = np.stack(xs), np.stack(ys)
    if shardings is None:
        return xs, ys

    def put(stack, sharding):
        if sharding is None:
            return stack
        if callable(sharding):
            return sharding(stack)
        import jax  # host-only module otherwise; keep cheap-import
        # Sharding objects reaching this branch are single-process (fully
        # addressable) by construction; multi-process engines pass the
        # pod-assembler CALLABLE above, so this device_put never launches
        # a collective off the worker thread.
        # reprolint: disable=RL003 reason=single-process sharding, see above
        return jax.device_put(stack, sharding)

    x_sharding, y_sharding = shardings
    return put(xs, x_sharding), put(ys, y_sharding)


# ---------------------------------------------------------------------------
# per-pod client views (multi-process / multi-pod runtime)
# ---------------------------------------------------------------------------

def pod_client_blocks(n_clients: int, n_pods: int) -> list[range]:
    """Static client-id blocks, one per pod: pod ``p`` owns clients
    ``[p * n/P, (p+1) * n/P)``.  Equal blocks are required — a ragged
    split would leave some shard without its client."""
    if n_pods < 1 or n_clients % n_pods:
        raise ValueError(
            f"n_clients={n_clients} must split evenly over "
            f"{n_pods} pods")
    per = n_clients // n_pods
    return [range(p * per, (p + 1) * per) for p in range(n_pods)]


def select_pod_blocked(rng: np.random.RandomState, blocks: list[range],
                       n_active: int) -> list[int]:
    """Pod-blocked client selection: each pod contributes
    ``n_active / n_pods`` clients drawn (without replacement) from its
    own block, concatenated in pod order — so active position ``j``
    always lands on pod ``j // (n_active / n_pods)`` and no sample ever
    crosses a pod boundary.  Every process runs this with the same RNG
    stream and gets the same list; the single-process executors accept
    the same policy (via :class:`PodClients`), which is what makes
    multi-process == single-process parity exact."""
    n_pods = len(blocks)
    if n_active % n_pods:
        raise ValueError(
            f"n_active={n_active} must split evenly over {n_pods} pods")
    per = n_active // n_pods
    active: list[int] = []
    for block in blocks:
        if per > len(block):
            raise ValueError(
                f"pod block {block} has {len(block)} clients; cannot "
                f"select {per}")
        draw = rng.choice(len(block), size=per, replace=False)
        active.extend(int(block.start + d) for d in draw)
    return active


class PodClients:
    """A (possibly partial) view of the global client population.

    ``pod=p`` (multi-process): ``loaders`` holds ONLY pod ``p``'s client
    block, in global-id order — each process constructs and advances just
    its own loaders, which is what keeps per-pod data loading honest.
    ``pod=None`` (single-process): ``loaders`` holds every client, and
    the view only switches the engine to the pod-blocked selection
    policy, so a one-host run reproduces the multi-process sample
    streams exactly."""

    def __init__(self, loaders: list[Loader], n_clients: int,
                 n_pods: int, pod: int | None = None):
        self.blocks = pod_client_blocks(n_clients, n_pods)
        self.n_clients = n_clients
        self.n_pods = n_pods
        self.pod = pod
        if pod is None:
            if len(loaders) != n_clients:
                raise ValueError(
                    f"pod=None view needs all {n_clients} loaders, got "
                    f"{len(loaders)}")
        else:
            if len(loaders) != len(self.blocks[pod]):
                raise ValueError(
                    f"pod {pod} owns {len(self.blocks[pod])} clients, got "
                    f"{len(loaders)} loaders")
        self.loaders = loaders

    @property
    def block(self) -> range:
        """Global client ids whose loaders live in this view."""
        return (range(self.n_clients) if self.pod is None
                else self.blocks[self.pod])

    def select(self, rng: np.random.RandomState,
               n_active: int) -> list[int]:
        """This round's GLOBAL active list under the pod-blocked policy
        (identical on every process for the same RNG stream)."""
        return select_pod_blocked(rng, self.blocks, n_active)

    def local_indices(self, active: list[int]) -> list[int]:
        """Positions in ``self.loaders`` for the subset of ``active``
        this view owns, in active order (== this pod's contiguous slice
        of the global draw, by :func:`select_pod_blocked`'s layout)."""
        block = self.block
        return [i - block.start for i in active if i in block]


def make_pod_clients(ds: Dataset, parts: list[np.ndarray], batch: int,
                     seed: int, *, n_pods: int,
                     pod: int | None = None) -> PodClients:
    """Per-pod client view over a (globally agreed) partition list: only
    ``pod``'s block of loaders is constructed, with global-id-keyed seeds
    (``pod=None`` builds all of them — the single-process comparator)."""
    blocks = pod_client_blocks(len(parts), n_pods)
    only = None if pod is None else blocks[pod]
    return PodClients(client_loaders(ds, parts, batch, seed, only=only),
                      len(parts), n_pods, pod)
