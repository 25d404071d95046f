"""Program spans: named host intervals on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``. Inside a profiler
session it records a host event on the same clock as the device's program
and op events, so a trace can say which layer of the program held each
stretch in which the device was idle; outside one it records nothing and
costs about a microsecond. Every name is in :data:`NAMES` and starts with
one of :data:`LAYERS` and a dot. Spans are opened per call, batch, chunk or
launch, never per row.
"""

from __future__ import annotations

import functools

import jax

LAYERS = ("driver", "consumer", "engine", "completion")

NAMES = (
    "driver.critical_points", "driver.discrete_gradient",
    "driver.morse_smale", "driver.ms.descending", "driver.ms.successors",
    "driver.ms.cofacets", "driver.ms.ascending_jump",
    "driver.ms.separatrices",
    "consumer.prefetch", "consumer.consume", "consumer.finalize",
    "consumer.reduce", "consumer.read_dev", "consumer.upload",
    "engine.init", "engine.dispatch", "engine.sync", "engine.integrate",
    "completion.complete", "completion.plan", "completion.execute",
    "completion.width_check",
)


def span(name: str, **meta):
    """Context manager recording ``name`` (with ``meta`` as the event's
    arguments) while a profiler session is on."""
    return jax.profiler.TraceAnnotation(name, **meta)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return run
    return wrap
