"""Named host spans on the profiler's clock.

`span(name)` marks one step of a service call.  While a JAX profiler
session runs (`jax.profiler.trace(dir)`, `jax.profiler.start_trace`, or a
capture through `jax.profiler.start_server(port)`), the span lands on the
calling thread's line of the trace's host plane, on the same clock as the
device's programs, so an idle gap on the device can be charged to the
step the host was in.  Without a session it records nothing.

The span names, and what each covers, are listed in
`src/repro/core/README.md` ("Tracing a live service").  A span is never
held open across a `yield`: the spans of one thread must nest.

This module never imports jax.  Where jax has not been imported (the
numpy engine on a host without it), `span` returns a shared null
context.
"""
from __future__ import annotations

import contextlib
import sys

_NULL = contextlib.nullcontext()


def span(name: str):
    """A context manager that marks `name` on the profiler's trace."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name)
