"""Backend dispatch for the fused Pallas kernels.

Every kernel in this package exists in (up to) three executable forms:

  * ``ref``        the pure-jnp oracle in ``repro.kernels.ref`` — runs on
                   any backend, is fully differentiable, and is the CPU
                   production path;
  * ``interpret``  the Pallas kernel under ``interpret=True`` — the kernel
                   *body* executes on the host, which validates the Pallas
                   program itself without TPU hardware (slow; CI parity
                   tests only);
  * ``pallas``     the Pallas kernel compiled through Mosaic — the TPU
                   production path.

One knob selects among them for the whole process:

    REPRO_KERNEL_BACKEND = auto | ref | interpret | pallas   (default auto)

``auto`` resolves to ``pallas`` on TPU and ``ref`` everywhere else (the
legacy ``REPRO_PALLAS_COMPILE=1`` escape hatch also forces ``pallas``).
``set_backend`` / the ``backend`` context manager override the environment
for tests and notebooks.  Kernels register here (see ``ops.py``) with an
optional ``supports`` predicate: shapes below kernel granularity always
take the reference path, matching the pre-dispatch behavior.
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from typing import Callable, Optional

from repro import compat

ENV_VAR = "REPRO_KERNEL_BACKEND"
VALID_BACKENDS = ("auto", "ref", "interpret", "pallas")
_CONCRETE = ("ref", "interpret", "pallas")

_override: Optional[str] = None


def _validate(name: str, source: str) -> str:
    b = name.strip().lower()
    if b not in VALID_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r} (from {source}); "
            f"valid backends: {', '.join(VALID_BACKENDS)}")
    return b


def get_backend() -> str:
    """The requested backend (may be 'auto'); env unless overridden."""
    if _override is not None:
        return _override
    return _validate(os.environ.get(ENV_VAR, "auto"), f"${ENV_VAR}")


def set_backend(name: Optional[str]) -> None:
    """Process-wide override of $REPRO_KERNEL_BACKEND (None clears it)."""
    global _override
    _override = None if name is None else _validate(name, "set_backend()")


@contextlib.contextmanager
def backend(name: str):
    """Scoped ``set_backend`` for tests."""
    global _override
    prev = _override
    set_backend(name)
    try:
        yield
    finally:
        _override = prev


def resolve(request: Optional[str] = None) -> str:
    """Concrete backend ('ref' | 'interpret' | 'pallas') for this call."""
    b = _validate(request, "argument") if request is not None else \
        get_backend()
    if b != "auto":
        return b
    if compat.is_tpu() or os.environ.get("REPRO_PALLAS_COMPILE") == "1":
        return "pallas"
    return "ref"


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Kernel:
    name: str
    ref: Callable                       # pure-jnp oracle
    pallas: Callable                    # accepts interpret=bool kwarg
    supports: Optional[Callable] = None  # (*args, **kw) -> bool


_REGISTRY: dict[str, Kernel] = {}


def register(name: str, *, ref: Callable, pallas: Callable,
             supports: Optional[Callable] = None) -> None:
    _REGISTRY[name] = Kernel(name, ref, pallas, supports)


def registered() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def call(name: str, *args, backend: Optional[str] = None,
         interpret: Optional[bool] = None, **kwargs):
    """Route one kernel invocation.

    ``interpret`` is the legacy per-call spelling kept for the existing
    wrapper signatures: True pins the interpret backend, False the
    compiled one; None defers to ``backend`` / the global knob."""
    try:
        k = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel {name!r} registered; known kernels: "
                       f"{', '.join(registered()) or '(none)'}") from None
    if interpret is not None:
        backend = "interpret" if interpret else "pallas"
    mode = resolve(backend)
    if mode == "ref" or (k.supports is not None
                         and not k.supports(*args, **kwargs)):
        return k.ref(*args, **kwargs)
    return k.pallas(*args, interpret=(mode == "interpret"), **kwargs)
