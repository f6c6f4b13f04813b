"""Async double-buffered prefetch of round-phase input stacks.

The scanned/sharded executors (``core/scan.py``) consume whole-phase input
stacks — ``(K, B, ...)`` labeled batches and ``(K, N, B, ...)`` client
slabs — that ``Loader.next_many`` / ``stack_client_batches_many`` assemble
synchronously on the host before every phase dispatch.  As the client
count N grows, that host-side stacking + H2D transfer is the dominant
serial cost of a round.  This module overlaps it with device execution:

  * :class:`Prefetcher` — the mechanism: one background worker thread
    pops build thunks off a request queue, runs them, and posts results
    into a *bounded depth-2 queue* (double buffering: one buffer being
    consumed by the device while the next is being assembled).  Worker
    exceptions are captured and re-raised in the consumer, and
    :meth:`Prefetcher.close` joins the thread — no prefetch thread
    outlives its owner (``tests/test_prefetch.py`` asserts this via
    ``threading.enumerate()``).

  * :class:`RoundPrefetcher` — the SemiSFL round policy on top: after
    round ``r``'s stacks are consumed it *speculates* round ``r+1``'s
    supervised and cross-entity stacks from (a) the K_s the engine just
    used, (b) an active-client subset drawn from a fork of the selection
    RNG (the engine's real draw in round ``r+1`` yields the same subset),
    and (c) the loaders' own restartable state.  Everything the worker
    draws is deterministic EXCEPT K_s, which the Eq. (10) controller may
    change after observing round ``r`` — so consumption validates the
    speculation descriptor against the actual request and, on mismatch,
    rolls the touched loaders back to their pre-speculation snapshots
    (``Loader.state_dict``) and rebuilds inline.  The prefetched and
    synchronous executors therefore consume bit-identical sample streams
    in every case, including K_s adaptation rounds and explicitly pinned
    ``active=`` sets.

The module stays cheap to import (no jax): device placement is injected
by the engine as ``sup_put`` / the ``cli_shardings`` that
``stack_client_batches_many`` already understands.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

from repro.data.pipeline import Loader, stack_client_batches_many
from repro.obs import span

THREAD_NAME = "repro-prefetch"
_SHUTDOWN = object()


def prefetch_default() -> bool:
    """``REPRO_PREFETCH`` — default OFF: the prefetcher assumes exclusive
    ownership of the loader objects between rounds (external draws from
    the same loaders would race the speculation)."""
    return os.environ.get("REPRO_PREFETCH", "0").lower() in (
        "1", "true", "on")


class PrefetchError(RuntimeError):
    """A prefetch worker build failed; the original exception is chained
    (``raise ... from``) and the worker thread has been shut down."""


class Prefetcher:
    """Background build pipeline: submit zero-arg thunks, get results in
    FIFO order.  ``depth`` bounds the result queue (2 = double buffer);
    the worker blocks rather than running unboundedly ahead.

    Timing accounting for the overlap metric: ``build_s`` accumulates
    worker-side seconds spent inside thunks, ``wait_s`` consumer-side
    seconds blocked in :meth:`get` — ``1 - wait_s / build_s`` is the
    fraction of host input work hidden behind device execution.
    """

    def __init__(self, *, depth: int = 2, name: str = THREAD_NAME):
        self._req: queue.Queue = queue.Queue()
        self._res: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._join_done = False
        self.build_s = 0.0
        self.wait_s = 0.0
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    @property
    def worker_alive(self) -> bool:
        return self._thread.is_alive()

    def _loop(self) -> None:
        while True:
            item = self._req.get()
            if item is _SHUTDOWN or self._stop.is_set():
                return
            tag, thunk = item
            t0 = time.perf_counter()
            try:
                payload, err = thunk(), None
            except BaseException as e:  # noqa: BLE001 — must reach consumer
                payload, err = None, e
            self.build_s += time.perf_counter() - t0
            # bounded put that stays responsive to close()
            while not self._stop.is_set():
                try:
                    self._res.put((tag, payload, err), timeout=0.1)
                    break
                except queue.Full:
                    continue

    def submit(self, tag: str, thunk: Callable[[], Any]) -> None:
        if self.closed:
            raise PrefetchError("submit() on a closed Prefetcher")
        self._req.put((tag, thunk))

    def get(self, timeout: Optional[float] = 600.0) -> tuple[str, Any]:
        """Next (tag, payload) in submission order.  A worker exception
        shuts the pipeline down and re-raises here, chained.  A worker
        that DIED without posting (thread crashed outside the build try,
        interpreter teardown killed the daemon) is detected immediately —
        the consumer must not sit out the full timeout on a pipeline that
        can never produce.  The blocking wait is the span
        ``semisfl.prefetch.wait``."""
        t0 = time.perf_counter()
        deadline = None if timeout is None else t0 + timeout
        try:
            with span("prefetch.wait"):
                tag, payload, err = self._wait(timeout, deadline)
        finally:
            self.wait_s += time.perf_counter() - t0
        if err is not None:
            self.close()
            raise PrefetchError(
                f"prefetch build {tag!r} failed in the worker") from err
        return tag, payload

    def _wait(self, timeout, deadline) -> tuple:
        """Block until the worker posts ``(tag, payload, err)``."""
        while True:
            try:
                return self._res.get(timeout=0.1)
            except queue.Empty:
                if not self._thread.is_alive():
                    self.close()
                    raise PrefetchError(
                        "prefetch worker died without posting a "
                        "result") from None
                if deadline is not None and \
                        time.perf_counter() >= deadline:
                    self.close()
                    raise PrefetchError(
                        f"prefetch worker produced nothing within "
                        f"{timeout}s (deadlocked or starved build?)"
                    ) from None

    def close(self) -> None:
        """Idempotent shutdown: unblocks and joins the worker thread.
        Safe to call any number of times in any pipeline state — a
        close after a worker fault (or after a timed-out join) is a
        cheap no-op, never a re-raise and never a second 10s join."""
        if self._stop.is_set() and (self._join_done
                                    or not self._thread.is_alive()):
            return
        self._stop.set()
        self._req.put(_SHUTDOWN)
        # drain so a worker blocked on a full result queue can exit
        while True:
            try:
                self._res.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)
        self._join_done = True

    def __del__(self):  # pragma: no cover — belt and braces
        try:
            self.close()
        except Exception:
            pass


class RoundPrefetcher:
    """Double-buffers SemiSFL round inputs over a fixed ``(labeled,
    client_loaders)`` binding (see module docstring for the speculation /
    cancel protocol).

    ``sup_put(xs, ys)`` runs on the worker and moves the supervised stack
    to device (the engine passes ``jnp.asarray``); ``cli_put(xs)``
    likewise for the vmapped executors' client stack; ``cli_shardings``
    is forwarded to :func:`stack_client_batches_many` for the sharded
    executor's direct-to-shard ``device_put``.
    """

    def __init__(self, labeled: Loader, client_loaders_: list[Loader], *,
                 k_u: int, n_active: int,
                 sup_put: Optional[Callable] = None,
                 cli_put: Optional[Callable] = None,
                 cli_shardings=None, depth: int = 2,
                 select_fn: Optional[Callable] = None):
        self.labeled = labeled
        self.loaders = client_loaders_
        self.k_u = k_u
        self.n_active = n_active
        self._sup_put = sup_put
        self._cli_put = cli_put
        self._cli_shardings = cli_shardings
        # custom active-set policy for speculation: ``select_fn(rng) ->
        # indices into self.loaders`` replacing the default global
        # ``rng.choice`` (the multi-pod engine passes its pod-blocked
        # policy restricted to this process's loaders; it must consume
        # the RNG stream exactly as the engine's own draw will)
        self._select_fn = select_fn
        self._pf = Prefetcher(depth=depth)
        # in-flight speculation descriptors, keyed by result tag:
        #   "sup" -> (k, labeled_snapshot)
        #   "cli" -> (active_tuple, k, {client_i: snapshot})
        self._spec: dict[str, tuple] = {}
        self.rounds = 0
        self.cancels = 0

    # -- builders (worker thread on speculation, caller thread inline);
    # each is a span on the thread that runs it
    def _build_sup(self, k: int):
        with span("batch.labeled"):
            xs, ys = self.labeled.next_many(k)
            return self._sup_put(xs, ys) if self._sup_put else (xs, ys)

    def _build_cli(self, active: list[int], k: int):
        with span("batch.clients"):
            xs, _ = stack_client_batches_many(self.loaders, active, k,
                                              shardings=self._cli_shardings)
            return self._cli_put(xs) if self._cli_put else xs

    # -- cancel/reshape protocol ---------------------------------------
    def _rollback(self, tag: str) -> None:
        """Undo a speculative build's loader draws (its result is being
        discarded): restore the pre-speculation snapshots.  Only safe
        once the build's result has been collected (or the worker
        joined) — the worker must not be mid-draw on these loaders.
        Tolerates a tag whose descriptor is already gone (a result that
        straggled in after its speculation was consumed or rolled back:
        there is nothing left to undo)."""
        spec = self._spec.pop(tag, None)
        if spec is None:
            return
        if tag == "sup":
            _, snap = spec
            self.labeled.load_state_dict(snap)
        else:
            _, _, snaps = spec
            for i, sd in snaps.items():
                self.loaders[i].load_state_dict(sd)

    def _pop(self, tag: str):
        """Blocking pop of the speculative result for ``tag``; discards +
        rolls back out-of-order results (a caller that aborted a round
        mid-way leaves the other tag's result queued first)."""
        while True:
            got, payload = self._pf.get()
            if got == tag:
                return payload
            self.cancels += 1
            self._rollback(got)

    # -- consumption (engine round driver) ------------------------------
    def get_supervised(self, k: int):
        """The ``(K, B, ...)`` labeled stacks for a phase of ``k``
        iterations.  Uses the speculative buffer when its K matches;
        otherwise (an Eq. (10) adaptation round changed the phase length
        after the worker had drawn) rolls the labeled stream back and
        rebuilds inline."""
        self.rounds += 1
        if "sup" not in self._spec:
            return self._build_sup(k)
        payload = self._pop("sup")
        k_spec, snap = self._spec.pop("sup")
        if k_spec == k:
            return payload
        self.cancels += 1
        self.labeled.load_state_dict(snap)
        return self._build_sup(k)

    def get_clients(self, active: list[int], k: int):
        """The ``(K, N, B, ...)`` client stacks for this round's active
        set.  Uses the speculative buffer when the forked-RNG subset and
        K match the actual request; otherwise restores the touched
        loaders and rebuilds inline."""
        if "cli" not in self._spec:
            return self._build_cli(list(active), k)
        payload = self._pop("cli")
        act_spec, k_spec, snaps = self._spec.pop("cli")
        if act_spec == tuple(int(a) for a in active) and k_spec == k:
            return payload
        self.cancels += 1
        for i, sd in snaps.items():
            self.loaders[i].load_state_dict(sd)
        return self._build_cli(list(active), k)

    def speculate(self, k_s: int,
                  select_rng: Optional[np.random.RandomState]) -> None:
        """Queue the NEXT round's builds.  Call after this round's stacks
        are consumed and the phase programs are dispatched — the worker
        assembles round ``r+1``'s inputs while round ``r`` executes.

        ``select_rng`` is the engine's host-side selection RandomState:
        it is *forked* (state copy), never advanced, so the engine's own
        draw next round sees an untouched stream and produces the same
        subset the speculation predicts."""
        if self._pf.closed or self._spec:
            return  # already speculating (caller retried) or shut down
        snap = self.labeled.state_dict()
        self._spec["sup"] = (k_s, snap)
        self._pf.submit("sup", lambda: self._build_sup(k_s))
        if self.k_u > 0 and select_rng is not None:
            fork = np.random.RandomState()
            fork.set_state(select_rng.get_state())
            if self._select_fn is not None:
                active = tuple(int(a) for a in self._select_fn(fork))
            else:
                active = tuple(int(a) for a in fork.choice(
                    len(self.loaders),
                    size=min(self.n_active, len(self.loaders)),
                    replace=False))
            snaps = {i: self.loaders[i].state_dict() for i in active}
            self._spec["cli"] = (active, self.k_u, snaps)
            self._pf.submit(
                "cli", lambda: self._build_cli(list(active), self.k_u))

    # -- lifecycle ------------------------------------------------------
    def stats(self) -> dict:
        """Counters for the bench harness; ``overlap_frac`` is the
        fraction of speculative host build time hidden behind device
        execution (1.0 = the consumer never waited)."""
        b, w = self._pf.build_s, self._pf.wait_s
        return {"rounds": self.rounds, "cancels": self.cancels,
                "spec_build_s": round(b, 6), "wait_s": round(w, 6),
                "overlap_frac": max(0.0, 1.0 - w / b) if b > 0 else 0.0}

    def close(self) -> None:
        """Join the worker and roll back any in-flight speculation, so
        the loaders are left exactly where the synchronous path would
        have them (the stream stays restartable).  Close-time rollbacks
        are not mispredictions and don't count as cancels.

        Never raises and never blocks on a pipeline that cannot produce:
        a worker that faulted (or died) mid-round is detected by
        ``Prefetcher.get`` immediately, after which the outstanding
        speculation is rolled back from the snapshots — the failed
        build's partial draws are undone, not replayed.  Every
        subsequent ``close()`` is a clean no-op."""
        if not self._pf.closed and self._pf.worker_alive:
            # collect finished results first so rollback can't race a
            # build still running in the worker
            try:
                while self._spec:
                    tag, _ = self._pf.get(timeout=60.0)
                    self._rollback(tag)
            except PrefetchError:
                pass  # worker faulted/died/starved: get() shut it down
        self._pf.close()
        if self._pf.worker_alive:
            # join timed out: a wedged build may still be mutating the
            # loaders — restoring snapshots under it would corrupt them,
            # so leave the (already abnormal) state alone
            self._spec.clear()
        for tag in list(self._spec):
            self._rollback(tag)

    @property
    def closed(self) -> bool:
        return self._pf.closed

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
