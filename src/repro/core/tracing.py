"""Host spans on the profiler's clock.

``span(name, **meta)`` marks a stretch of host work as a named event in
the JAX profiler's trace, with ``meta`` as the event's stats, so host
work and device operations share one timeline.  It is a thin wrapper over
``jax.profiler.TraceAnnotation`` and the only place in the package that
touches ``jax.profiler``.  Metadata known only once the work is done is
added with ``set_metadata(**meta)`` on the entered span::

    with span("serve.admit", rid=req.rid) as sp:
        info = engine.admit(req, pad)
        sp.set_metadata(cached=info["cached"])

Tracing is off unless a profiler session is active: a span then costs one
TraceMe construction (under a microsecond on the host) and records
nothing.  There is no flag or environment variable; an operator turns it
on the way JAX documents it, around the code to trace::

    jax.profiler.start_trace("/tmp/trace")    # or: with jax.profiler.trace(..)
    handler.run(requests)
    jax.profiler.stop_trace()

or with ``jax.profiler.start_server(port)`` and a capture from
TensorBoard's profile plugin.  The trace's ``.xplane.pb`` then holds the
spans on the host plane and the device ops on each device plane.

The serving path's spans (``launch/serve.py``, ``core/dispatch.py``) are
named ``serve.*``; ``docs/benchmarks.md`` lists them with their metadata.
Spans on one thread nest properly.
"""
from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` with ``meta`` as a host
    event while a profiler session is active, and nothing otherwise."""
    return jax.profiler.TraceAnnotation(name, **meta)
