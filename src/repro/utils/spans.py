"""Host spans on the profiler's own clock.

``span("prefilter.dispatch", cand=1024)`` is a
``jax.profiler.TraceAnnotation`` named ``pipeann.prefilter.dispatch``:
it lands on the host plane of the capture that ``jax.profiler.trace``
(or ``start_trace``/``stop_trace``) writes, on the thread that opened
it, beside the device ops on the same clock. Outside a capture it
records nothing and costs about a microsecond. ``stats`` hold what is
known when the span opens (batch number, mechanism, widths).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "pipeann."


def span(name: str, **stats) -> TraceAnnotation:
    """A context manager that records ``pipeann.<name>`` while a
    profiler capture runs."""
    return TraceAnnotation(PREFIX + name, **stats)
