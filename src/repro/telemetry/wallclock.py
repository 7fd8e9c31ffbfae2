"""Wall-clock spans of the program, on the device trace's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler records, it lands on the trace's host plane with ``meta`` as its
stats; otherwise it records nothing.  The profiler is the store.  The
spans, their places and their readers are listed in DESIGN.md §10.6.
"""
from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with ``meta`` as its stats; use it as a
    context manager."""
    return jax.profiler.TraceAnnotation(name, **meta)
