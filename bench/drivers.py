"""The window machinery that every mode shares.

A mode is a file ``modes/<mode>.py``, named by a traffic mix's ``mode``; its
``run(graph, config, traffic, seeds, seconds, trace_dir, compiles, log,
devices)`` drives the program through one window and returns the result the
harness reads: ``kind`` (a file ``kinds/<kind>.py`` that judges it),
``window`` (a :class:`Window`), ``attempted``, ``failed``, and what its kind
reads.  Here: the program's ``IOStats`` counters read at both ends of the
window, the compiles inside it, the device trace (``--trace 1``), and host
spans in that trace.  A new mode imports these and changes none of them.
"""

from __future__ import annotations

import time

import numpy as np

import jax

import tracing

clock = time.perf_counter

#: IOStats fields read at both ends of the window
COUNTERS = (
    "steps_sampled",
    "bucket_executions",
    "time_slots",
    "pipeline_stall_slots",
    "block_ios",
    "block_bytes",
    "ondemand_ios",
    "ondemand_bytes",
    "walk_bytes_written",
    "walk_bytes_read",
    "pinned_block_hits",
    "exec_time",
    "supersteps",
)

#: jax.monitoring event of one program compiled or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def snapshot(stats) -> dict:
    return {k: getattr(stats, k) for k in COUNTERS}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in COUNTERS}


class CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent cache)."""

    def __init__(self):
        self.total = 0
        self._open_at = None
        self.in_window = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.total += 1

    def open(self) -> None:
        self._open_at = self.total

    def close(self) -> None:
        self.in_window = self.total - self._open_at


#: the device trace covers the window's first seconds only: a trace of the
#: whole window holds millions of events, and reading it would outlast a run
TRACE_SECONDS = 20.0


class Window:
    """Both ends of a window: clock, counters, compiles, optional trace."""

    def __init__(self, stats, compiles: CompileCounter, trace_dir):
        self.stats = stats
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.t_open = self.t_close = None
        self.trace_s = None
        self._tracing = False

    def open(self) -> None:
        self.c_open = snapshot(self.stats)
        self.compiles.open()
        if self.trace_dir is not None:
            tracing.start(self.trace_dir)
            self._tracing = True
        self.t_open = clock()

    def boundary(self) -> None:
        """At a call boundary: end the trace once it is long enough."""
        if self._tracing and clock() - self.t_open >= TRACE_SECONDS:
            self._stop_trace()

    def _stop_trace(self) -> None:
        self.trace_s = clock() - self.t_open
        self.c_trace = snapshot(self.stats)
        self._tracing = False
        tracing.stop()

    def close(self) -> None:
        self.t_close = clock()
        self.c_close = snapshot(self.stats)
        self.compiles.close()
        if self._tracing:
            self._stop_trace()

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def counters(self) -> dict:
        return delta(self.c_open, self.c_close)

    @property
    def trace_counters(self) -> dict:
        return delta(self.c_open, self.c_trace)


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace is running)."""
    return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name)


class WindowClosed(Exception):
    """Raised from the advance wrapper to end a job at the window's close."""


def filled(corpus: np.ndarray) -> np.ndarray:
    return (corpus >= 0).sum(1)
