"""Program spans: the recorder, and the spans the bi-block engine records.

The recorder (``repro.core.spans``) hangs off ``IOStats``; the engine's
spans sit at its layer boundaries, at most 8 records per advance call, and
the spans that different per-layer metrics sum never overlap.
"""

import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BiBlockEngine, IOStats, partition_into_n_blocks, rmat, rwnv_task
from repro.core.spans import COMPILE_PREFIX, SpanRecorder
from repro.engines.step import advance_pair
from repro.io import BlockStore, write_and_open

#: the main-thread spans each per-layer metric sums (``bench/metrics/``),
#: plus the device call; no two of them may overlap
METRIC_GROUPS = {
    "pack_upload": ("advance.pack", "advance.upload"),
    "device": ("advance.device",),
    "fetch_record": ("advance.fetch", "advance.record"),
    "block_view": (
        "blocks.get_view",
        "blocks.partial_view",
        "blocks.extend_view",
        "blocks.gather_view",
        "blocks.schedule",
    ),
    "pool_wait": ("pool.acquire", "pool.push"),
    "route": ("slot.route", "buckets.split"),
}


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_nesting_and_the_exception_path():
    stats = IOStats()
    with pytest.raises(KeyError):
        with stats.span("outer", 3) as outer:
            with stats.span("inner"):
                pass
            raise KeyError("left inside the span")
    recs = stats.spans.records(-np.inf, np.inf)
    assert [s.name for s in recs] == ["inner", "outer"]
    inner, outer_rec = recs
    assert outer_rec.t0 <= inner.t0 <= inner.t1 <= outer_rec.t1
    assert outer_rec.n == 3 and (outer.t0, outer.t1) == (outer_rec.t0, outer_rec.t1)
    assert stats.spans.totals["outer"][0] == 1
    assert stats.spans.seconds("outer") == pytest.approx(outer_rec.t1 - outer_rec.t0)
    stats.reset()
    assert stats.spans.records(-np.inf, np.inf) == [] and stats.spans.totals == {}


def test_abutting_spans_share_a_clock_reading():
    rec = SpanRecorder()
    with rec.span("a") as a:
        pass
    with rec.span("b", t0=a.t1) as b:
        pass
    assert b.t0 == a.t1 and rec.busy(a.t0, b.t1) == pytest.approx(b.t1 - a.t0)


def test_thread_names():
    rec = SpanRecorder()

    def work():
        with rec.span("worker"):
            pass

    t = threading.Thread(target=work, name="span-test-worker")
    t.start()
    t.join()
    with rec.span("main"):
        pass
    by_name = {s.name: s.thread for s in rec.records(-np.inf, np.inf)}
    assert by_name == {"worker": "span-test-worker", "main": threading.current_thread().name}
    assert rec.thread_of("worker") == "span-test-worker" and rec.thread_of("nothing") is None


def test_concurrent_adds_lose_nothing():
    rec = SpanRecorder()
    threads, per_thread = 16, 500
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [
            threading.Thread(target=lambda: [rec.add("s", 0.0, 1.0) for _ in range(per_thread)])
            for _ in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
            assert not w.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert rec.totals["s"] == [threads * per_thread, float(threads * per_thread)]
    assert len(rec.records(-1.0, 2.0)) == threads * per_thread


def test_clipping_to_an_interval():
    rec = SpanRecorder()
    rec.add("a", 0.0, 4.0)
    rec.add("a", 5.0, 6.0)
    rec.add("b", 3.0, 8.0)
    rec.add("c", 9.0, 10.0)
    assert rec.window(2.0, 7.0) == pytest.approx({"a": 3.0, "b": 4.0})
    # union of [2,4], [5,6], [3,7]
    assert rec.busy(2.0, 7.0) == pytest.approx(5.0)
    assert rec.window(2.0, 7.0, thread="elsewhere") == {}


def test_none_after_a_drop():
    rec = SpanRecorder(capacity=3)
    for k in range(5):
        rec.add("s", float(k), k + 0.5)
    assert rec.dropped == 2 and rec.totals["s"][0] == 5
    # records [0, 0.5] and [1, 1.5] are gone: a window reaching them is unknown
    assert rec.window(0.0, 10.0) is None and rec.busy(1.2, 10.0) is None
    assert rec.records(1.5, 10.0) is not None
    assert rec.window(2.0, 10.0) == pytest.approx({"s": 1.5})


def test_compile_listener_records_each_build_once():
    # each recorder registers itself; a second listener would record twice
    a, b = IOStats(), IOStats()

    def spans_test_build(x):
        return x * 3 + 1

    f = jax.jit(spans_test_build)
    f(jnp.arange(5)).block_until_ready()
    f(jnp.arange(5)).block_until_ready()  # cached: no second build
    for stats in (a, b):
        builds = [
            s for s in stats.spans.records(-np.inf, np.inf)
            if s.name.startswith(COMPILE_PREFIX) and "spans_test_build" in s.name
        ]
        assert len(builds) == 1 and builds[0].t1 >= builds[0].t0


# ---------------------------------------------------------------------------
# the engine's spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kron10():
    """Graph500 Kronecker graph at scale 10 in 8 edge-balanced blocks."""
    return partition_into_n_blocks(rmat(10, 16, 0.57, 0.19, 0.19, seed=0), 8)


def _run_counted(disk, async_pipeline: bool):
    task = rwnv_task(p=4.0, q=0.25, walks_per_vertex=1, length=20, seed=3)
    sources = np.arange(0, disk.num_vertices, 2, dtype=np.int64)
    engine = BiBlockEngine(
        disk,
        task,
        pool="disk",
        block_cache_blocks=4,
        record_walks=True,
        async_pipeline=async_pipeline,
        initial_walks=sources,
    )
    calls = []
    advance = engine._advance

    def counted(batch, wid, alive=None):
        calls.append(len(batch))
        return advance(batch, wid, alive)

    engine._advance = counted
    res = engine.run()
    return engine, res, calls


@pytest.mark.parametrize("async_pipeline", [True, False], ids=["async", "serial"])
def test_engine_spans(tmp_path, kron10, async_pipeline):
    with write_and_open(kron10, str(tmp_path / "g")) as disk:
        engine, res, calls = _run_counted(disk, async_pipeline)
    stats = engine.stats
    recs = stats.spans.records(-np.inf, np.inf)
    assert stats.spans.dropped == 0
    main = threading.current_thread().name
    mine = [s for s in recs if s.thread == main]

    advances = [s for s in mine if s.name == "advance"]
    assert [s.n for s in advances] == calls
    assert sum(s.name == "pool.acquire" for s in mine) == stats.time_slots
    for name in ("advance.pack", "advance.upload", "advance.device", "advance.fetch", "advance.record"):
        assert sum(s.name == name for s in mine) == len(calls)
    assert any(s.name == "slot.route" for s in mine) and any(s.name == "pool.push" for s in mine)
    assert any(s.name == "blocks.get_view" for s in mine)
    assert any(s.name == "blocks.partial_view" for s in mine)

    # exec_time is the device call plus the copy back, nothing else
    device_fetch = sum(s.t1 - s.t0 for s in mine if s.name in ("advance.device", "advance.fetch"))
    assert abs(stats.exec_time - device_fetch) <= 1e-9

    # the spans different metrics sum never overlap
    grouped = sorted(
        (s for s in mine for names in METRIC_GROUPS.values() if s.name in names),
        key=lambda s: s.t0,
    )
    for earlier, later in zip(grouped, grouped[1:]):
        assert later.t0 >= earlier.t1, (earlier, later)

    # nothing per vertex, pread, hop or trace column: at most 8 records a call
    for adv in advances:
        inside = [s for s in mine if adv.t0 <= s.t0 and s.t1 <= adv.t1]
        assert len(inside) <= 8, inside

    # background jobs carry their threads' names
    threads = {s.thread for s in recs if s.name == "blocks.build"}
    assert all(t.startswith("blockstore-prefetch") for t in threads)
    if async_pipeline:
        assert {s.thread for s in recs if s.name == "pool.apply"} == {"walkpool-writer"}
    else:
        assert not any(s.name == "pool.apply" for s in recs)

    c = res.block_store_counters
    assert c["sync_materialize_time"] == stats.spans.seconds("blocks.materialize")
    assert c["prefetch_wait_time"] == stats.spans.seconds("blocks.prefetch_wait")


def test_fetch_span_counts_the_advance_lane_iterations(tmp_path, kron10, monkeypatch):
    """The ``n`` of each ``advance.fetch`` is the call's ``lane_iters``, and
    ``IOStats.advance_lane_iters`` sums them."""
    from repro.engines import base

    counts = []
    advance_pair = base.advance_pair

    def counted(*args, **kw):
        out = advance_pair(*args, **kw)
        counts.append(int(out[6]))
        return out

    monkeypatch.setattr(base, "advance_pair", counted)
    with write_and_open(kron10, str(tmp_path / "g")) as disk:
        engine, res, calls = _run_counted(disk, async_pipeline=False)
    stats = engine.stats
    fetches = [s.n for s in stats.spans.records(-np.inf, np.inf) if s.name == "advance.fetch"]
    assert len(counts) == len(calls) > 0
    assert fetches == counts
    assert stats.advance_lane_iters == sum(counts) == res.stats.as_dict()["advance_lane_iters"]
    # every step took one lane of one iteration
    assert stats.advance_lane_iters >= stats.steps_sampled > 0


def test_blockstore_counters_are_span_totals(small_blocked):
    stats = IOStats()
    store = BlockStore(small_blocked, stats, capacity=2, enable_prefetch=True)
    try:
        store.get_view(0)  # materialised on this thread
        store.prefetch(1)
        store.get_view(1)  # joins the prefetch
        store.partial_view(2, np.arange(small_blocked.block_starts[2], small_blocked.block_starts[2] + 5))
    finally:
        store.close()
    c = store.counters()
    assert c["sync_materialize_time"] == stats.spans.seconds("blocks.materialize") > 0
    assert c["prefetch_wait_time"] == stats.spans.seconds("blocks.prefetch_wait") > 0
    assert stats.spans.totals["blocks.materialize"][0] == 2  # block 0, partial of block 2
    assert stats.spans.totals["blocks.build"][0] == 1
    views = [s for s in stats.spans.records(-np.inf, np.inf) if s.name == "blocks.get_view"]
    assert [s.n for s in views] == [int(small_blocked.block_nverts[b]) for b in (0, 1)]


# ---------------------------------------------------------------------------
# named scopes of the device advance
# ---------------------------------------------------------------------------

def test_advance_scopes_in_the_compiled_program():
    i32 = jnp.int32
    V, E, N = 64, 256, 512  # two stages: the compaction is in the program

    def s(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    args = (
        s((2 * V,)), s((2,)), s((2,)), s((2 * (V + 1),)), s((2,)), s((2 * E,)), s((2,)),
        s((1,)), s((1,), jnp.float32),
        s((N,)), s((N,)), s((N,)), s((N,)), s((N,), jnp.bool_),
        jax.random.PRNGKey(0), s(()), s((), jnp.float32), s((), jnp.float32), s((), jnp.float32),
    )
    lowered = advance_pair.lower(
        *args, order=2, k_max=4, n_iters=9, v_iters=8, record=True, has_alias=False, max_len=10
    )
    op_names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    for scope in ("advance.locate", "advance.propose", "advance.hop", "advance.compact"):
        assert any(scope in name for name in op_names), scope
