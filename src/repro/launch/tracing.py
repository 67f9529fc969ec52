"""Host spans of the serving loop, on the profiler's clock.

``span`` is ``jax.profiler.TraceAnnotation``: the profiler records it only
while a trace is running (``jax.profiler.start_trace``); otherwise it costs
one context manager.  ``gc_spans`` puts a span ``repro.host.gc`` round
every garbage collection while it is entered.  The device's ops carry the
program's layer scopes (``jax.named_scope``) on the same clock.
"""
from __future__ import annotations

import contextlib
import gc

from jax.profiler import TraceAnnotation

span = TraceAnnotation


@contextlib.contextmanager
def gc_spans():
    """A ``repro.host.gc`` span round each collection inside the block;
    the collector's callback is removed on leaving it, also on an error."""
    running = []

    def hook(phase, info):
        if phase == "start":
            running.append(span("repro.host.gc",
                                generation=info["generation"]))
            running[-1].__enter__()
        elif running:
            running.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
