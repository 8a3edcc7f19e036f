"""Drive the system under test through one window of a batch job.

The driver takes the program only through its public entry point
(``BiBlockEngine``) and the engine's own advance seam, and records around it
with the benchmark's clock: the window's two ends, the program's ``IOStats``
counters at both, the compiles inside, the device trace (``--trace 1``) and,
for the reference, the walks the window produced.

The window opens at the first advance-call completion after the
initialization stage and ``warmup_supersteps`` supersteps, and closes at the
first completion ``seconds`` later; the run stops there.
"""

from __future__ import annotations

import time

import numpy as np

import jax

import tracing
from generator import job_sources

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


def run_job(sut, config: dict, traffic: dict, seeds, seconds: float, trace_dir, compiles, log):
    from repro.core.transition import rwnv_task
    from repro.engines.biblock import BiBlockEngine

    walk, engine_cfg, storage = config["walk"], config["engine"], config["storage"]
    t0 = clock()
    sources = job_sources(sut.num_vertices, traffic, walk["walks_per_vertex"])
    task = rwnv_task(
        p=walk["p"],
        q=walk["q"],
        walks_per_vertex=walk["walks_per_vertex"],
        length=walk["length"],
        seed=seeds.walk,
    )
    engine = BiBlockEngine(
        sut.disk,
        task,
        pool=storage["walk_pool"],
        block_cache_blocks=storage["block_cache_blocks"],
        loading=engine_cfg["loading"],
        async_pipeline=engine_cfg["async_pipeline"],
        k_max=engine_cfg["k_max"],
        record_walks=engine_cfg["record_walks"],
        initial_walks=sources,
    )
    log("engine_s", clock() - t0)
    t_warm = clock()
    warmup = int(traffic.get("warmup_supersteps", 1))
    win = Window(engine.stats, compiles, trace_dir)
    state = {"calls": 0}
    ended = []
    advance = engine._advance

    def observed(batch, wid, alive=None):
        with annotate("advance"):
            out = advance(batch, wid, alive)
        if win.t_open is None:
            if engine.stats.supersteps > warmup:
                log("warmup_s", clock() - t_warm)
                state["filled_open"] = filled(engine.corpus)
                win.open()
        else:
            state["calls"] += 1
            # walks that this call retired, for the reference to judge
            was = np.ones(len(wid), bool) if alive is None else np.asarray(alive, bool)
            ended.append(np.asarray(wid)[was & ~np.asarray(out[1], bool)])
            win.boundary()
            if clock() - win.t_open >= seconds:
                win.close()
                state["filled_close"] = filled(engine.corpus)
                raise WindowClosed
        return out

    engine._advance = observed
    try:
        engine.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the job ended before its window closed; give it more walks")
    return {
        "kind": "batch",
        "window": win,
        "attempted": state["calls"],
        "failed": 0,
        "corpus": engine.corpus,
        "sources": sources,
        "filled_open": state["filled_open"],
        "filled_close": state["filled_close"],
        "ended": np.concatenate(ended) if ended else np.zeros(0, np.int64),
        "walk": walk,
        "k_max": engine_cfg["k_max"],
    }


DRIVERS = {"job": run_job}
