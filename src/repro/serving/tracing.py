"""Profiler spans of the serving program itself.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: a host event on the
profiler's host plane, on the same clock as the device planes, recorded
only while a profiler session runs (``jax.profiler.start_trace`` …
``stop_trace``, or ``jax.profiler.trace``). With no session it records
nothing and costs under a microsecond, so the spans stay in the code for
good and need no switch.

Every name starts with ``repro.``; its second part is the layer whose
own work the span covers (docs/RUNTIME.md §11):

- ``repro.driver.*``: the serving driver's loop (``driver.py``);
- ``repro.pool.*``: one pool iteration (``runtime.py``);
- ``repro.engine.*``: one engine iteration (``engine.py``);
- ``repro.scheduler.*``: one scheduler decision (``bcedge.py``);
- ``repro.python.gc``: a garbage collection, on whatever thread ran it.
"""
from __future__ import annotations

from typing import Optional

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    """A context manager that records ``name`` over its body while the
    profiler runs."""
    return TraceAnnotation(name)


class GcSpans:
    """A ``gc.callbacks`` entry covering each collection with the span
    ``repro.python.gc`` (collections never nest, so one is open at most)."""

    def __init__(self):
        self._open: Optional[TraceAnnotation] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = span("repro.python.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
