"""Program spans: named intervals of host work on the profiler's clock.

Every span the server, the planner and the engine emit goes through
:func:`span`, a :class:`jax.profiler.TraceAnnotation`.  Under a profiler
session (``jax.profiler.trace(dir)``) each span lands in the trace's host
plane on the thread that ran it, on the same clock as the device's
operations, so device idle time can be put down to the span open on the
serving thread.  Without a session a span records nothing and costs about a
microsecond.  ``docs/metrics.md`` lists the span tree and its arguments.

Spans mark layer boundaries (a tick, a read's compile, a row range's kernel
call), never a tile or a row.
"""

from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager marking ``name`` with ``args`` (arguments that are
    ``None`` are left out).  ``set_metadata(**more)`` on the entered span
    adds arguments known only at its end."""
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in args.items() if v is not None})
