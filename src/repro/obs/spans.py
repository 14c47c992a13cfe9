"""Program spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: inside a
``jax.profiler.trace`` session each span lands in the same ``.xplane.pb``
as the device operations, on the same clock, so an idle gap of the device
can be named by the host work that spans it.  Outside a session a span
costs one enter and one exit of a native object; its keyword arguments
(job, iteration, primitive, storage, bytes) are encoded into the event
only while a session is active, so pass them as plain values.

Every name carries the ``tensile.`` prefix and is part of the interface:
``SPANS`` lists them, and analyses of a trace find them by name.

``TelemetryHub`` and ``TraceRecorder`` are the planner's measurement
plane and the virtual-clock exporter; spans are neither.
"""
from __future__ import annotations

import contextlib
import gc
import threading

from jax.profiler import TraceAnnotation as span

SPANS = (
    # executor (core/executor.py), one iteration and its parts
    "tensile.iteration", "tensile.place_inputs", "tensile.ensure",
    "tensile.dispatch", "tensile.sync", "tensile.swap_out",
    "tensile.swap_in", "tensile.recompute", "tensile.retire",
    "tensile.hot_swap", "tensile.fetch_outputs",
    # DMA channel (core/engine.py): one hold of the channel, on the thread
    # that makes the copy
    "tensile.transfer",
    # controller (core/multiplexer.py), between iterations
    "tensile.report_telemetry", "tensile.replan", "tensile.executor_init",
    # the garbage collector, while a controller has a live job
    "tensile.gc",
)

_gc_lock = threading.Lock()
_gc_users = 0
# the collection in progress: one at a time, on the thread that runs it
_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("tensile.gc", generation=info["generation"])
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


@contextlib.contextmanager
def gc_spans():
    """Name each garbage collection ``tensile.gc`` for the block.  Nested
    and concurrent blocks share one ``gc.callbacks`` entry, removed when
    the last block ends."""
    global _gc_users
    with _gc_lock:
        if _gc_users == 0:
            gc.callbacks.append(_on_gc)
        _gc_users += 1
    try:
        yield
    finally:
        with _gc_lock:
            _gc_users -= 1
            if _gc_users == 0:
                gc.callbacks.remove(_on_gc)
                _gc_open.clear()
