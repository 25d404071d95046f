"""Counting XLA compiles through JAX's monitoring events."""

from __future__ import annotations


class CompileMeter:
    """Counts XLA backend compiles and their seconds while installed (a
    ``with`` block). A program loaded from the persistent compilation
    cache is not a backend compile and is not counted."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)
