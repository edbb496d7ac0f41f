"""Host spans the benchmark puts around its calls into the program.

They are ``jax.profiler.TraceAnnotation`` events, so they land in the
profiler's trace on the same clock as the device's operations, and the
trace reduction attributes device idle time to them.  With no profiler
running they cost a flag check.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def span(name: str):
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
