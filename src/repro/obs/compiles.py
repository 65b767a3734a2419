"""Compile spans: where a process's time goes to JAX tracing, lowering
and backend compilation.

JAX reports each stage of building a program as a time-span event
(``jax.monitoring``).  ``install()`` registers one listener for the whole
process that turns each of the three stages into one closed span on a
process-level ``Tracer``:

* ``/jax/core/compile/jaxpr_trace_duration``         -> ``compile.trace``
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` -> ``compile.lower``
* ``/jax/core/compile/backend_compile_duration``      -> ``compile.backend``

The span's name is the function JAX names (``fun_name``).  The backend
event wraps the persistent-cache lookup too, so a program loaded from
that cache is also a ``compile.backend`` span.  A jitted function traced
inside another's trace gives a span inside the outer one, so seconds
spent compiling are the union of the spans, not their sum.

Importing ``repro.s2m3`` installs the listener, so a process that builds
a deployment records its compiles from before the first one: the
weights' and the warm-up's as well as the served programs'.  JAX
reports only events that happen after a listener is registered.

JAX stamps these events with the wall clock; they are moved onto the
tracer's clock (``perf_counter`` by default) by one offset taken at
install.  The spans belong to no request, so they stay out of the
serving scheduler's per-request tracer.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

from repro.obs.trace import Tracer

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}


class CompileRecorder:
    """Compile spans on one clock.  ``clock`` is the tracer's clock;
    ``wall`` is the clock JAX stamps its events with."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 wall: Callable[[], float] = time.time):
        self.tracer = Tracer(clock=clock)
        self._offset = wall() - clock()

    def on_span(self, event: str, start: float, end: float,
                **kwargs) -> None:
        stage = STAGES.get(event)
        if stage is None:
            return
        self.tracer.record(str(kwargs.get("fun_name", "")), stage,
                           start - self._offset, end - self._offset)


_lock = threading.Lock()
_recorder: CompileRecorder | None = None


def install() -> CompileRecorder:
    """The process's recorder, registered with ``jax.monitoring`` on the
    first call; later calls return it and register nothing."""
    global _recorder
    with _lock:
        if _recorder is None:
            import jax

            rec = CompileRecorder()
            jax.monitoring.register_event_time_span_listener(rec.on_span)
            _recorder = rec
        return _recorder


def recorder() -> CompileRecorder | None:
    """The installed recorder, or None before the first ``install()``."""
    return _recorder
