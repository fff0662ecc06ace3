"""Host spans of the device path, written into the JAX profiler's trace.

The rest of :mod:`repro.serving.obs` observes the simulator on its
simulated clock. This module marks what the real serving path does on the
host (``Executor.generate_bucketed``) with ``jax.profiler.TraceAnnotation``,
so the spans land on the profiler's clock beside the device's operations.

Names follow ``<component>.<phase>``:

- ``executor.prepare``: bucketing and padding, the synthetic conditioning,
  the per-request keys, the segment bounds and the pipeline lookup;
- ``executor.dispatch``: the noise and segment calls, which enqueue device
  work; inside it one ``executor.segment`` per segment call, with the stats
  ``role`` and ``steps``;
- ``executor.fetch``: the wait for the result, its copy to the host and
  the slice to the request count.

With no profiler running, :func:`span` returns one shared no-op context and
formats nothing.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

PREPARE = "executor.prepare"
DISPATCH = "executor.dispatch"
FETCH = "executor.fetch"
SEGMENT = "executor.segment"

_OFF = contextlib.nullcontext()
_active = TraceAnnotation.is_enabled  # the profiler's own check, no Python state


def span(name: str, **stats):
    """A context that records ``name`` with ``stats`` in the profiler's
    trace while one is being taken, and does nothing otherwise."""
    if not _active():
        return _OFF
    return TraceAnnotation(name, **stats)
