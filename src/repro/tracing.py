"""Named host spans of the program, on the profiler's clock.

``span("profile.key")`` marks one phase as the host event
``repro.profile.key``: a ``jax.profiler.TraceAnnotation``, recorded beside
the device's ``XLA Ops`` while a profiler trace runs (``jax.profiler.trace``
or ``start_trace``) and costing about a microsecond when none runs. Spans
nest by time on the thread that opens them. Keyword arguments ride along as
the event's stats (``span("codesign", arch="mixtral_8x7b")``).

There is no switch: running a trace is what turns the spans on. jax is
never imported here. A process that has not imported it cannot be tracing,
so a span is then a no-op, and numpy-only paths stay free of jax.
"""

from __future__ import annotations

import contextlib
import functools
import sys

PREFIX = "repro."


def span(name: str, **stats):
    """A context manager that records ``repro.<name>`` while a trace runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def traced(name: str):
    """Decorator: every call of the function inside ``span(name)``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
