"""Program spans: the one mechanism behind the phase timers and the trace.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation`` that also
times itself on the host clock:

    with tracing.span("spjoin.map") as t:
        ...
    result.map_time_s = t.seconds

While a profiler trace is being taken (``jax.profiler.trace``), each span
lands on the host thread's line of the trace, on the clock the device's
``XLA Ops`` events use, nested in the span that encloses it on the same
thread; its counts are the event's metadata. Counts known only at the end
go in through ``add``. Untraced, a span costs about 2 µs and records no
counts: pass only values that are already on the host, so that a span
never adds a device read or a sync.

``root(name, **counts)`` opens the span of one request (a join, a query
batch, a build) with ``request=<n>`` from a process-wide counter, so that
the spans of one request share an identifier in the trace.
"""
from __future__ import annotations

import itertools
import time

from jax.profiler import TraceAnnotation

_requests = itertools.count(1)
_enabled = TraceAnnotation.is_enabled


class span(TraceAnnotation):
    """A timed profiler span; ``seconds`` is its host time once it ends."""

    seconds: float = 0.0

    def __init__(self, name: str, **counts: int | float | str):
        # Counts cost even untraced when passed to the annotation, so they
        # go in only while a trace is being taken.
        if counts and _enabled():
            super().__init__(name, **counts)
        else:
            super().__init__(name)

    def __enter__(self) -> "span":
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        super().__exit__(*exc)

    def add(self, **counts: int | float | str) -> None:
        """Counts known only at the end of the span (traced runs only)."""
        if _enabled():
            self.set_metadata(**counts)


def root(name: str, **counts: int | float | str) -> span:
    """The span of one request, with ``request=<n>`` added to its counts."""
    return span(name, request=next(_requests), **counts)
