"""``job``: one window of a batch job through the program's ``BiBlockEngine``.

The cell's graph is written to a block file and opened through the program
(:class:`harness.SystemUnderTest`); the engine reads it from there.  The mode
takes the program only through that public entry point and the engine's own
advance seam, and records around it with the benchmark's clock: the window's
two ends, the program's ``IOStats`` counters at both, the compiles inside,
the device trace (``--trace 1``) and, for the reference, the walks the window
produced.  Its result has the kind ``batch`` (``kinds/batch.py``).

The window opens at the first advance-call completion after the
initialization stage and ``warmup_supersteps`` supersteps, and closes at the
first completion ``seconds`` later; the run stops there.
"""

from __future__ import annotations

import numpy as np

from drivers import Window, WindowClosed, annotate, clock, filled
from generator import job_sources
from harness import SystemUnderTest


def run(graph, config: dict, traffic: dict, seeds, seconds: float, trace_dir, compiles, log, devices):
    """The job over ``graph`` written to a block file; ``devices`` go unused,
    as the engine runs on JAX's default device."""
    sut = SystemUnderTest(config, graph, log)
    try:
        return run_job(sut, config, traffic, seeds, seconds, trace_dir, compiles, log)
    finally:
        sut.close()


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
