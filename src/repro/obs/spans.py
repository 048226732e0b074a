"""Wall-clock spans on the profiler's clock (docs/observability.md, "Wall
spans").

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``. While a
profiler trace is being taken (``jax.profiler.trace`` or
``start_trace``) it records one host event, written by the same session
and on the same clock as the device's events, so an idle gap on the
device can be put down to the phase the host was in. With no trace
running it records nothing and costs under a microsecond. A span that
opens before the trace starts, or closes after it stops, is not
recorded.

Keyword arguments become the event's stats (``rid=7``); the event's name
stays as given. They are evaluated whether or not anything is recorded,
so pass only values that are already at hand (a step index, a request
id), never one built for the span; the decode tick's ``live_rows``, a
numpy sum over the slots, is the one exception.

The virtual-clock :class:`~repro.obs.trace.Tracer` stays the
deterministic replay timeline; wall spans are for where the wall time
and the chip's time go.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]


def span(name: str, **args) -> TraceAnnotation:
    """A context manager that records ``name`` (with ``args`` as stats)
    while a profiler trace is running."""
    return TraceAnnotation(name, **args)
