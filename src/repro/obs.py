"""Host spans of the SemiSFL round, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``semisfl.<name>``:
the profiler records its start, end and thread, and nesting on one
thread gives its parent.  With no trace running a span costs about a
microsecond.  Spans are opened on the host only, never inside a jitted
or scanned function.  ``bench/spans.py`` reads them back from a trace.

    round                 SemiSFLSystem.run_round, the whole call
    batch.labeled         labeled draw, stack and host-to-device put
    batch.clients         client draw, stack and put
    prefetch.wait         the driver blocked on the prefetch worker
    phase.supervised      dispatch of the supervised phase program
    phase.cross_entity    dispatch of the cross-entity phase program
    broadcast             bottoms to the active clients
    fedavg                FedAvg of the client bottoms
    sync                  a device-to-host read
    eval                  SemiSFLSystem.evaluate, the whole call

A scope is the other kind of name: ``jax.named_scope("semisfl.<name>")``
around model code inside a jitted or scanned function.  It runs only
while tracing and leaves ``semisfl.<name>`` in the ``op_name`` metadata
of every device operation the code lowers to, backward passes and
vmapped clients included (``transpose(jvp(semisfl.model.fc))/...``), so
a device trace can sum those operations' time.  It is not a span: it
has no host event.

    model.conv            the conv stacks, max-pools included
    model.fc              average pool, FC stack and classifier
"""
from __future__ import annotations

import functools

PREFIX = "semisfl."


def span(name: str, **stats):
    """Context manager for the span ``semisfl.<name>``; keyword arguments
    become the event's stats in the trace."""
    # imported here so that data/ stays importable without jax
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(PREFIX + name, **stats)


def spanned(name: str):
    """Decorator: run the whole call inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def scope(name: str):
    """Context manager naming the device operations traced inside it
    ``semisfl.<name>``; for use inside jitted or scanned functions."""
    import jax
    return jax.named_scope(PREFIX + name)
