"""Program spans on the profiler's clock.

``span(name, **ids)`` is ``jax.profiler.TraceAnnotation``: a host span that
costs about a microsecond when no profiler runs and, under
``jax.profiler.start_trace``, lands in the same trace as the device's
operations, its keyword arguments as the event's stats (``layer=k``,
``rid=...``).  Spans of one thread nest; the nesting is the parent link.
Nothing is stored besides the profiler's own trace.

The numpy core runs without JAX; there ``span`` does nothing.

Names are ``<module>.<what>``: ``fsi.*`` on the fleet call
(``faas/simulator.run_fsi`` and ``core/fsi``), ``payload.*`` in the payload
codec (``faas/payload``, which the fleet call and the layer pipeline share),
``serve.*`` on the serving loop (``serving/scheduler``).  ``docs/ARCHITECTURE.md``
(Observability) lists them.
"""

from __future__ import annotations

import functools

try:
    from jax.profiler import TraceAnnotation as span
except ImportError:  # the numpy core without the accelerator extra
    class span:  # type: ignore[no-redef]
        def __init__(self, name: str, **ids):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc) -> bool:
            return False

__all__ = ["span", "spanned"]


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
