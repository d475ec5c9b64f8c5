"""Program spans, on the profiler's clock.

`span(name, **ids)` is a `jax.profiler.TraceAnnotation` while a profiler
trace runs (`jax.profiler.start_trace`): a host event on the line of the
thread that entered it, in the same xplane trace as the device's XLA ops,
so both share one clock.  Its ids (`req=`, `reqs=`, `group=`) are the
event's metadata, which `jax.profiler.ProfileData` reads back as the
event's `stats`: the spans of one request share its id whatever thread
they ran on, and nesting on one thread gives a span's parent.  Nothing is
buffered here; the profiler writes the spans out when the trace stops.
With no trace running a span is a shared no-op context manager (about
half a microsecond on a CPU core, against one for an idle
`TraceAnnotation`).

The served path's spans, from the client's thread down (docs/architecture.md
§10): `server.submit` (client), `server.tick_wait` (the flusher, while a
coalescing window is open), and on a pool thread `server.group` holding
`cache.resolve` (`cache.compile` on a miss), `query.bind`,
`query.dispatch`, `query.fetch`, `query.feedback`, `query.fallback` (an
overflow's re-run, nesting the same spans), `query.decode` and
`server.settle`.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str, **ids):
    """A context manager that records `name` with `ids` while a profiler
    trace runs, and does nothing otherwise."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **ids)
    return _OFF
