"""Scan-compiled phase executor: the round-as-one-XLA-program builder.

The paper's wall-clock claim (3.8x) is about engine time, not Python
dispatch — so a phase of K iterations must be ONE compiled program, not K
jitted calls with a host sync each.  :func:`scan_phase` wraps any
per-iteration ``step_fn(carry, batch) -> (carry, out)`` into a jitted

    phase(carry, batches) -> (carry, stacked_outs)

that ``lax.scan``s over the leading ``K`` axis of every leaf in
``batches``, carrying the training state on-device with buffer donation.
The host reads ``stacked_outs`` once, not once per step; the engine
reads both phases' outputs in one transfer at the round's end.

Both the classification engine (``core/engine.py`` supervised + cross-
entity phases) and the LM-task train step (``launch/steps.py``) build
their phase executors here, so a later PR can shard the scanned round's
client axis in one place.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Tuple, Union

import jax

Carry = Any
Batch = Any


def default_unroll() -> Union[int, bool]:
    """Scan unroll policy (overridable via ``REPRO_SCAN_UNROLL``).

    Default is the rolled loop (unroll=1): compile time stays flat in
    ``K`` and the loop construct is what the client-axis sharding PR will
    scan over.  Measured on the 2-core CI-class CPU: rolled is ~3x faster
    than a per-step jitted loop on the dispatch-bound smoke config, but
    XLA:CPU compiles the *larger* smoke CNN's conv fwd/bwd ~2x slower
    inside a ``while`` loop — set ``REPRO_SCAN_UNROLL=full`` (or an
    integer factor) to trade compile time for that back.
    """
    env = os.environ.get("REPRO_SCAN_UNROLL", "auto").lower()
    if env in ("auto", "0", "false", "off", "1"):
        return 1                      # rolled loop (the default)
    if env in ("true", "full"):
        return True
    try:
        n = int(env)
    except ValueError:
        raise ValueError(
            f"unknown REPRO_SCAN_UNROLL {env!r}; valid: auto, full, or a "
            "positive integer unroll factor") from None
    if n < 1:
        raise ValueError(
            f"REPRO_SCAN_UNROLL must be >= 1, got {n}")
    return n


def _named_scan(step_fn: Callable, unroll: Union[int, bool, None],
                name: str) -> Callable:
    """``phase(carry, batches)``: ``lax.scan`` of ``step_fn`` over the
    leading K axis, named ``name`` for jit."""
    if unroll is None:
        unroll = default_unroll()

    def phase(carry: Carry, batches: Batch):
        return jax.lax.scan(step_fn, carry, batches, unroll=unroll)

    phase.__name__ = phase.__qualname__ = name
    return phase


def scan_phase(step_fn: Callable[[Carry, Batch], Tuple[Carry, Any]], *,
               donate_carry: bool = True,
               unroll: Union[int, bool, None] = None,
               jit: bool = True, name: str = "phase"
               ) -> Callable[[Carry, Batch], Tuple[Carry, Any]]:
    """Build a compiled K-iteration phase from a single-iteration step.

    ``step_fn`` must be a pure ``(carry, batch) -> (carry, out)``
    function (the same one a per-step reference loop jits), ``batches`` a
    pytree whose leaves all share a leading ``K`` axis.  Retraces happen
    only when ``K`` or the batch shapes change (e.g. when the Eq. (10)
    controller shrinks ``K_s``) — a handful of compilations per run.

    ``donate_carry`` donates the input carry's buffers to the output so
    params/optimizer/queue update in place on accelerators (no-op where
    the backend does not support donation).  ``unroll`` is forwarded to
    ``lax.scan`` (``None`` -> :func:`default_unroll`).  ``name`` names
    the jitted program: its HLO module and device-trace events read
    ``jit_<name>``.
    """
    phase = _named_scan(step_fn, unroll, name)
    if not jit:
        return phase
    return jax.jit(phase, donate_argnums=(0,) if donate_carry else ())


def pinned_scan_phase(step_fn: Callable[[Carry, Batch], Tuple[Carry, Any]],
                      *, carry_shardings, out_shardings,
                      donate_carry: bool = True,
                      unroll: Union[int, bool, None] = None,
                      jit: bool = True, name: str = "phase"
                      ) -> Callable[[Carry, Batch], Tuple[Carry, Any]]:
    """:func:`scan_phase` with jit-level output-sharding pins and NO
    phase-level ``shard_map``.

    This is the phase shape for steps that mix a *manual* ``shard_map``
    subregion with GSPMD model-parallel computation (the model-sharded LM
    train step in ``launch/steps.py``): on JAX 0.4.37, the repo's former
    floor, XLA's SPMD partitioner rejects ``while`` loops inside
    partially-manual regions (``Check failed:
    sharding.IsManualSubgroup()``), so the scan
    must stay OUTSIDE the manual region — the step body enters/leaves its
    own fully-manual ``shard_map`` each iteration, and the layer-stack
    scans inside the model run under plain GSPMD.

    ``carry_shardings`` / ``out_shardings`` are NamedSharding pytrees
    matching the carry and the K-stacked per-step outputs.  Pinning them
    keeps GSPMD from re-committing the model-parallel parameters (or
    tagging replicated metrics with degenerate data-axis shardings) and
    makes phase ``k+1`` see identically-committed inputs — same
    no-spurious-recompile argument as :func:`sharded_scan_phase`."""
    phase = _named_scan(step_fn, unroll, name)
    if not jit:
        return phase
    return jax.jit(phase, donate_argnums=(0,) if donate_carry else (),
                   out_shardings=(carry_shardings, out_shardings))


def sharded_scan_phase(step_fn: Callable[[Carry, Batch], Tuple[Carry, Any]],
                       *, mesh, carry_specs, batch_specs, out_specs,
                       donate_carry: bool = True,
                       unroll: Union[int, bool, None] = None,
                       jit: bool = True, name: str = "phase"
                       ) -> Callable[[Carry, Batch], Tuple[Carry, Any]]:
    """:func:`scan_phase` compiled under ``shard_map`` over ``mesh``.

    The whole K-iteration phase — scan included — runs inside one
    ``shard_map`` region, so ``step_fn`` sees its *local* block of any
    carry/batch leaf whose spec names mesh axes (the client-stacked
    bottoms and the ``(K, N, B, ...)`` client batches shard the client
    axis over the data axes) and the full value of every replicated leaf
    (top/proj/teacher/queue/rng/step).  ``step_fn`` is responsible for its
    own collectives: in the cross-entity step the per-client bottom
    updates need none, the top/proj gradients are one psum-mean, and the
    queue write all-gathers the (tiny) projected features.

    ``carry_specs`` / ``batch_specs`` / ``out_specs`` are PartitionSpec
    pytrees matching ``carry``, ``batches`` and the stacked per-step
    outputs (see ``repro.sharding.specs.semi_carry_pspecs``).  The replication
    check is off: replicated outputs are established via psum."""
    from repro.compat import shard_map

    phase = _named_scan(step_fn, unroll, name)
    mapped = shard_map(phase, mesh=mesh,
                       in_specs=(carry_specs, batch_specs),
                       out_specs=(carry_specs, out_specs),
                       check_vma=False)
    if not jit:
        return mapped
    # Pin the jit-level output shardings to the declared specs: without
    # this GSPMD may tag replicated outputs with degenerate data-axis
    # shardings, so the NEXT round's phase (and the supervised phase fed
    # from the same state) sees differently-committed inputs and
    # recompiles — one spurious multi-second compile per executor.
    from jax.sharding import NamedSharding, PartitionSpec as _P
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             (carry_specs, out_specs),
                             is_leaf=lambda x: isinstance(x, _P))
    return jax.jit(mapped, donate_argnums=(0,) if donate_carry else (),
                   out_shardings=shardings)
