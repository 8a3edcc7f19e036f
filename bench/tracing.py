"""Device trace of the window: capture, and the reduction to metrics.

:func:`load_events` reads the profiler's ``.xplane.pb`` into plain event
tuples; :func:`reduce_events` turns them into the numbers the per-layer
readers and the result's ``device``/``breakdown`` use.  The reduction works on
the tuples alone, so a small recorded trace checks it without a chip.

* ``busy_s``: union of the intervals of the device's ``XLA Ops`` events,
  averaged over the device planes.
* ``advance_s``: summed duration of the ``XLA Modules`` events whose name
  holds ``pair_advance`` (the jit module of ``pair_advance_impl``).
* ``module_s``: device seconds of each ``XLA Modules`` name, its
  ``(<hash>)`` suffix stripped (``jit_pair_advance_impl``), averaged over the
  device planes as ``advance_s`` is: a reader finds its own program's
  device time by the module's name.
* ``device_ops``: the ten op names with the most device time.
* ``idle_gaps``: the ten longest gaps between busy intervals, each named by
  the benchmark's own host span that covers its middle
  (``bench:advance`` = inside the program's advance call, host side:
  packing, upload, dispatch, copy back; another ``bench:`` span when only
  that one covers it; otherwise ``host``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

import numpy as np

#: (plane, line, name, start_ns, duration_ns)
Event = Tuple[str, str, str, float, float]

ADVANCE_MODULE = "pair_advance"
SPAN_PREFIX = "bench:"

#: the program hash XLA appends to a module's name: ``jit_f(1234)``
_MODULE_HASH = re.compile(r"\([^()]*\)$")


def start(directory: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_events(directory: str) -> List[Event]:
    """Device op/module events and the benchmark's host spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    events: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                # an op's event name is its HLO text: keep the op's own name
                name = ev.name.split(" = ", 1)[0]
                if host and not name.startswith(SPAN_PREFIX):
                    continue
                events.append((plane.name, line.name, name, ev.start_ns, ev.duration_ns))
    return events


def _merge(starts: np.ndarray, ends: np.ndarray):
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.nonzero(new)[0]
    return s[idx], run_end[np.r_[idx[1:] - 1, s.size - 1]]


def _span_at(spans: List[Event], t: float) -> str:
    name = "host"
    for _, _, sname, s, d in spans:
        if s <= t <= s + d:
            if sname == SPAN_PREFIX + "advance":
                return sname
            name = sname
    return name


def reduce_events(events: Iterable[Event]) -> Optional[dict]:
    """Device numbers of a traced window; ``None`` when no device op ran."""
    events = list(events)
    ops = [e for e in events if e[1] == "XLA Ops"]
    if not ops:
        return None
    spans = [e for e in events if e[2].startswith(SPAN_PREFIX)]
    planes = sorted({e[0] for e in ops})
    busy_ns = 0.0
    gaps = []
    for plane in planes:
        st = np.array([e[3] for e in ops if e[0] == plane], np.float64)
        du = np.array([e[4] for e in ops if e[0] == plane], np.float64)
        m_s, m_e = _merge(st, st + du)
        busy_ns += float((m_e - m_s).sum())
        for a, b in zip(m_e[:-1], m_s[1:]):
            gaps.append((b - a, a, b))
    busy_s = busy_ns / len(planes) / 1e9
    totals: dict = {}
    for e in ops:
        totals[e[2]] = totals.get(e[2], 0.0) + e[4]
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    modules = [e for e in events if e[1] == "XLA Modules"]
    advance_ns = sum(e[4] for e in modules if ADVANCE_MODULE in e[2])
    module_ns: dict = {}
    for e in modules:
        name = _MODULE_HASH.sub("", e[2])
        module_ns[name] = module_ns.get(name, 0.0) + e[4]
    gaps.sort(key=lambda g: -g[0])
    idle = [[_span_at(spans, (a + b) / 2), g / 1e9] for g, a, b in gaps[:10]]
    return {
        "busy_s": busy_s,
        "advance_s": advance_ns / len(planes) / 1e9,
        "module_s": {name: ns / len(planes) / 1e9 for name, ns in module_ns.items()},
        "device_ops": [[name, ns / 1e9] for name, ns in device_ops],
        "idle_gaps": idle,
        "devices": len(planes),
    }
