"""Program spans: where the host's time goes, layer by layer.

One :class:`SpanRecorder` hangs off every :class:`~repro.core.stats.IOStats`
(``stats.spans``); ``with stats.span(name, n):`` times a block of code at a
layer boundary.  A span

* enters ``jax.profiler.TraceAnnotation(name)``, so it lands on the
  profiler's host plane beside the device ops of any profile taken;
* appends a :class:`Span` ``(name, t0, t1, thread, n)`` to a bounded log on
  exit, an exception included (``t0``/``t1`` from ``time.perf_counter``);
* adds to the per-name cumulative ``(count, seconds)`` totals.

Every program build (``jax.monitoring``'s backend-compile event) is recorded
as a span ``compile:<fun_name>`` ending when the event fires.  One listener
per process routes it to every live recorder.

Readers clip the log to an interval: :meth:`SpanRecorder.window` gives
per-name seconds, :meth:`SpanRecorder.busy` the union of the spans, both
``None`` once the log has dropped a record that reached into the interval.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax

__all__ = ["COMPILE_PREFIX", "Span", "SpanRecorder"]

clock = time.perf_counter

#: records the log keeps; older ones are dropped and counted
CAPACITY = 65536

#: name prefix of the spans recorded for program builds
COMPILE_PREFIX = "compile:"

#: jax.monitoring event fired once per program built (compiled or loaded
#: from the persistent cache), with the build's duration and ``fun_name``
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    thread: str
    n: int


class _Open:
    """One open span; ``t0``/``t1`` stay readable after the block exits."""

    __slots__ = ("_rec", "name", "n", "t0", "t1", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, n: int, t0: Optional[float]):
        self._rec = rec
        self.name = name
        self.n = n
        self.t0 = t0
        self.t1 = None

    def __enter__(self) -> "_Open":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.t0 is None:
            self.t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock()
        self._ann.__exit__(*exc)
        self._rec.add(self.name, self.t0, self.t1, self.n)


class SpanRecorder:
    """Bounded, thread-safe log of spans plus per-name totals."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self.clear()
        _route_compiles(self)

    def clear(self) -> None:
        with self._lock:
            self._log: deque = deque()
            #: records dropped from the front of the log
            self.dropped = 0
            # the latest end of a dropped record: windows opening before it
            # may have lost records
            self._dropped_until = float("-inf")
            #: name -> [count, seconds] over every record ever added
            self.totals: Dict[str, list] = {}

    def span(self, name: str, n: int = 0, *, t0: Optional[float] = None) -> _Open:
        """Time a ``with`` block as ``name``.  ``n`` (settable on the handle
        inside the block) counts the block's work items; ``t0`` starts the
        span at an earlier clock reading, so that two spans can abut."""
        return _Open(self, name, n, t0)

    def add(self, name: str, t0: float, t1: float, n: int = 0) -> None:
        """Append one record from the calling thread."""
        rec = (name, t0, t1, _thread_name(), int(n))
        with self._lock:
            if len(self._log) >= self.capacity:
                old = self._log.popleft()
                self.dropped += 1
                self._dropped_until = max(self._dropped_until, old[2])
            self._log.append(rec)
            tot = self.totals.get(name)
            if tot is None:
                self.totals[name] = [1, t1 - t0]
            else:
                tot[0] += 1
                tot[1] += t1 - t0

    def seconds(self, name: str) -> float:
        """Cumulative seconds of ``name`` (0.0 if never recorded)."""
        tot = self.totals.get(name)
        return 0.0 if tot is None else tot[1]

    # -- readers -------------------------------------------------------------------
    def records(self, t_a: float, t_b: float, thread: Optional[str] = None) -> Optional[List[Span]]:
        """Records overlapping ``[t_a, t_b]`` (on ``thread`` if given), or
        ``None`` if a dropped record may have overlapped it."""
        with self._lock:
            if self._dropped_until > t_a:
                return None
            log = list(self._log)
        return [
            Span(*s) for s in log if s[2] > t_a and s[1] < t_b and (thread is None or s[3] == thread)
        ]

    def window(self, t_a: float, t_b: float, thread: Optional[str] = None) -> Optional[Dict[str, float]]:
        """Per-name seconds inside ``[t_a, t_b]``, each record clipped to it."""
        recs = self.records(t_a, t_b, thread)
        if recs is None:
            return None
        out: Dict[str, float] = {}
        for s in recs:
            out[s.name] = out.get(s.name, 0.0) + min(s.t1, t_b) - max(s.t0, t_a)
        return out

    def busy(self, t_a: float, t_b: float, thread: Optional[str] = None) -> Optional[float]:
        """Seconds of ``[t_a, t_b]`` covered by at least one record."""
        recs = self.records(t_a, t_b, thread)
        if recs is None:
            return None
        total, end = 0.0, t_a
        for s in sorted(recs, key=lambda s: s.t0):
            lo, hi = max(s.t0, end), min(s.t1, t_b)
            if hi > lo:
                total += hi - lo
            end = max(end, hi)
        return total

    def thread_of(self, name: str) -> Optional[str]:
        """The thread that recorded the latest ``name`` record."""
        with self._lock:
            for s in reversed(self._log):
                if s[0] == name:
                    return s[3]
        return None


_local = threading.local()


def _thread_name() -> str:
    try:
        return _local.name
    except AttributeError:
        _local.name = threading.current_thread().name
        return _local.name


# -- program builds -------------------------------------------------------------------
_live: "weakref.WeakSet[SpanRecorder]" = weakref.WeakSet()
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    t1 = clock()
    name = COMPILE_PREFIX + str(kw.get("fun_name", "?"))
    for rec in list(_live):
        rec.add(name, t1 - duration, t1)


def _route_compiles(rec: SpanRecorder) -> None:
    """Register the one compile listener of the process, on first use."""
    global _listening
    _live.add(rec)
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
