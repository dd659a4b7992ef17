"""Bridge from the span tracer (``obs.trace``) to the JAX profiler's clock.

``install()`` does two things for a tracer (the process-wide ``TRACER``
by default), once:

  * sets ``tracer.annotate`` to ``jax.profiler.TraceAnnotation``, so every
    span entered while the tracer is enabled also opens a profiler
    annotation of the same name. Any ``jax.profiler`` capture (TensorBoard,
    Perfetto, a benchmark's traced run) then shows ``router.pump``,
    ``engine.step`` and the rest on the host timeline beside the device's
    ops;
  * registers a ``jax.monitoring`` duration listener that turns each
    backend compile into a retroactive ``runtime.compile`` complete event
    (``fun``: the compiled function's name). It returns at once while the
    tracer is disabled.

``obs.trace`` itself stays free of jax; the serving stack calls
``install()`` at import.
"""
from __future__ import annotations

import time

import jax

from .trace import TRACER, Tracer

__all__ = ["install", "COMPILE_EVENT"]

#: the event JAX records around each XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _compile_listener(tracer: Tracer):
    def on_duration(event: str, secs: float, **kw) -> None:
        if not tracer.enabled or event != COMPILE_EVENT:
            return
        dur_us = int(secs * 1e6)
        tracer.complete(
            "runtime.compile", time.monotonic_ns() // 1000 - dur_us, dur_us,
            cat="runtime", fun=str(kw.get("fun_name", "?")),
        )

    return on_duration


def install(tracer: Tracer = TRACER) -> None:
    """Put ``tracer``'s spans on the profiler's clock and its compiles on
    its timeline. Idempotent: a tracer with an ``annotate`` hook is left
    as it is."""
    if tracer.annotate is not None:
        return
    tracer.annotate = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_duration_secs_listener(_compile_listener(tracer))
