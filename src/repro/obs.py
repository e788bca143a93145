"""Host spans on the profiler's clock, and the garbage collector's clock.

``span(name, **meta)`` opens a host span in JAX's profiler trace, on the
same clock as the device planes, so an idle gap on the device can be
named by what the host was doing in it. With no trace running a span
costs about a microsecond, so spans go only around batches and chunks,
never around single requests or plans. Nesting on one thread gives the
parent. The names are stable:

- ``repro.planserve.wait``: PlanServer blocked for a first request, then
  filling the batch until its deadline;
- ``repro.planserve.batch``: one PlanServer batch, planned and resolved
  (meta ``batch``, its ordinal, and ``size``, its requests);
- ``repro.planserve.resolve``: setting the batch's futures, callbacks
  included;
- ``repro.planner.lookup``: canonical keys, arena hits and LRU moves;
- ``repro.planner.dispatch``: packing masks and enqueuing every chunk's
  device merge;
- ``repro.planner.sync``: host blocked on one chunk's device outputs;
- ``repro.planner.decode``: host decode of one chunk's partition tensors;
- ``repro.planner.host_plan``: host ``plan()`` of the misses on a fabric
  or objective the device path does not take;
- ``repro.gc``: one garbage collection (meta ``generation``);
- ``repro.xsim.lower``: xsim lowering, planning and compiling every
  (workload, algorithm) pair, then stacking them;
- ``repro.xsim.run``: the xsim cycle scan, until its outputs are on the
  host.

The collector clock is one per process: ``install_gc_clock()`` adds it to
``gc.callbacks`` once, and ``snapshot()`` returns what it has counted.
"""
from __future__ import annotations

import functools
import gc
import threading
import time
from typing import NamedTuple


@functools.cache
def _annotation():
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def span(name: str, **meta):
    """A host span in the profiler's trace; ``meta`` becomes its stats."""
    return _annotation()(name, **meta)


class GCStats(NamedTuple):
    """Collections since the clock was installed: seconds paused,
    collections, and collections of the oldest generation."""

    pause_s: float
    collections: int
    full_collections: int


class _GCClock:
    # Collections do not nest and each one starts and stops on the thread
    # that triggered it, so one open span at a time is enough.
    def __init__(self):
        self.pause_s = 0.0
        self.collections = 0
        self.full_collections = 0
        self._open = None
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = span("repro.gc", generation=info["generation"])
            self._open.__enter__()
            self._t0 = time.perf_counter()
        elif self._open is not None:
            self.pause_s += time.perf_counter() - self._t0
            self.collections += 1
            if info["generation"] == 2:
                self.full_collections += 1
            self._open.__exit__(None, None, None)
            self._open = None


_CLOCK = _GCClock()
_INSTALL_LOCK = threading.Lock()


def install_gc_clock() -> None:
    """Add the process's collector clock to ``gc.callbacks`` (once)."""
    _annotation()  # import jax here, never inside a collection
    with _INSTALL_LOCK:
        if _CLOCK not in gc.callbacks:
            gc.callbacks.append(_CLOCK)


def snapshot() -> GCStats:
    return GCStats(_CLOCK.pause_s, _CLOCK.collections, _CLOCK.full_collections)
