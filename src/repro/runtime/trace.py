"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: a no-op
unless a profiler session is running, and then an event in the same
trace as the device's operations.  ``args`` become the event's metadata
(formatted only while a session is active).  Nothing is recorded
outside the profiler, and nothing here waits for the device.
"""

from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **args)
