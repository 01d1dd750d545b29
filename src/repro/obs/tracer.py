"""Trace spans and op scopes for the hot paths (DESIGN.md §15).

Spans wrap host *dispatch* boundaries in ``jax.profiler.TraceAnnotation``
so the library's front-end stages show up as named ranges in a jax
profiler / perfetto capture, on the same clock as the device's ops.  When
telemetry is disabled (the default) :func:`span` returns a shared no-op
context manager: no allocation, no profiler calls, nothing.

Spans are never opened inside jitted code: under jit the Python body runs
only at trace time, so an in-program annotation would label tracing, not
execution (why-no-instrumentation-inside-jit, DESIGN.md §15).  Inside a
traced program :meth:`Tracer.named_scope` names the ops instead: the
executor wraps every batch of its plans in ``repro.exec.<op family>``, which
reaches each compiled op's metadata (``op_name``, and the profiler trace's
``tf_op``) and changes nothing else, so it is always on.
"""

from __future__ import annotations

import contextlib

import jax

# NOT ``from repro.obs import registry`` — the package re-exports a
# same-named *function*, which shadows the submodule attribute.
from repro.obs.registry import enabled as _obs_enabled

try:  # pragma: no cover - present on every supported jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:  # pragma: no cover
    _TraceAnnotation = None

_NULL = contextlib.nullcontext()


def span(name: str):
    """Context manager: a profiler trace annotation when enabled, no-op off."""
    if not _obs_enabled() or _TraceAnnotation is None:
        return _NULL
    return _TraceAnnotation(name)


class Tracer:
    """Span factory with a fixed name prefix.

    >>> tr = Tracer("repro.serve")
    >>> with tr.span("wave"):
    ...     dispatch_wave()
    """

    def __init__(self, prefix: str = "repro") -> None:
        self.prefix = prefix

    def span(self, name: str):
        return span(f"{self.prefix}.{name}")

    def named_scope(self, name: str):
        """jax.named_scope — for use INSIDE traced code (names jaxpr ops);
        unconditional because it costs nothing at execution time."""
        return jax.named_scope(f"{self.prefix}.{name}")
