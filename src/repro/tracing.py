"""Host spans on the JAX profiler's clock.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation`` once JAX is
loaded, so a span lands in the same trace as the device programs launched
inside it, and its keyword arguments come back as the event's stats. While
no profiler session runs a span costs one check; while one runs (the
benchmark's ``--trace 1``, ``jax.profiler.start_trace``, a profiler server)
it is recorded when it closes. Where JAX was never imported (the runtime
and the analysis suite run without it) the span is a shared no-op: tracing
never imports JAX.
"""

from __future__ import annotations

import sys
from typing import Any

__all__ = ["span"]


class _NoSpan:
    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set_metadata(self, **attrs: Any) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **attrs: Any):
    """A context manager that records ``name`` with ``attrs`` in the JAX
    profiler's trace, or does nothing where JAX is not loaded."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **attrs)
