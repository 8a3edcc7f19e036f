"""The staged async bi-block pipeline: bit-identity, fault and gauge pins.

The async pipeline (walk-pool writer thread + next-slot pool drain/bucket
split preloads + plan-driven view prefetches) must be *observationally
identical* to the serial reference mode: same walks, same corpus, same
deterministic block/on-demand charges — across both pool backends, both
graph backends, and every pool shard count (the sharded pool partitions
the keyspace across per-shard sequenced writers; walk-spill charges are
additionally invariant across shard counts).  A writer-thread fault must
propagate out of ``run()``, remove any disk-pool spill directories, and
``close()`` must neither raise nor hang.
"""

import os
import threading

import numpy as np
import pytest

from repro.core import (
    BiBlockEngine,
    IOStats,
    WalkBatch,
    deepwalk_task,
    erdos_renyi,
    partition_into_n_blocks,
    rwnv_task,
)
from repro.core.scheduler import TimeSlotPlan
from repro.engines.pipeline import BucketCursor
from repro.io import AsyncWalkPool, MemoryWalkPool
from repro.testing import given, settings, st


def _result_sig(res):
    return (
        res.endpoint_counts.tobytes(),
        None if res.corpus is None else res.corpus.tobytes(),
        res.stats.steps_sampled,
        res.stats.block_ios,
        res.stats.block_bytes,
        res.stats.ondemand_ios,
        res.stats.ondemand_bytes,
    )


# ---------------------------------------------------------------------------
# Property: async pipeline == serial reference, across the backend matrix
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    nv=st.integers(60, 140),
    nblocks=st.integers(2, 5),
    flush=st.sampled_from([0, 16, 1 << 18]),
    shards=st.sampled_from([1, 2, 4]),
)
def test_async_pipeline_bitwise_identical_to_serial(seed, nv, nblocks, flush, shards):
    """async x {memory, disk} pool x {ram, disk} graph x pool_shards {1,2,4}
    == serial, bitwise, on random graphs — at spill-every-push, mid, and
    never-spill thresholds.  Every sharded run is compared to the same
    single-writer serial reference, so walks and block/on-demand charges
    are transitively bit-identical across shard counts too."""
    import shutil
    import tempfile

    from repro.io import DiskBlockedGraph, write_block_file

    g = erdos_renyi(nv, nv * 5, seed=seed)
    bg = partition_into_n_blocks(g, nblocks)
    tmp = tempfile.mkdtemp(prefix="grasorw_pipe_")
    try:
        path = os.path.join(tmp, f"g_{seed}_{nv}_{nblocks}.grb")
        write_block_file(bg, path)
        task = rwnv_task(p=3.0, q=0.5, walks_per_vertex=1, length=6, seed=seed)
        ref = _result_sig(
            BiBlockEngine(
                bg, task, record_walks=True, async_pipeline=False, pool_flush_walks=flush
            ).run()
        )
        for pool in ("memory", "disk"):
            for backend in ("ram", "disk"):
                bgx = bg if backend == "ram" else DiskBlockedGraph(path)
                res = BiBlockEngine(
                    bgx,
                    task,
                    record_walks=True,
                    async_pipeline=True,
                    pool=pool,
                    pool_flush_walks=flush,
                    pool_shards=shards,
                    pool_dir=os.path.join(tmp, f"pool_{pool}_{backend}_{shards}"),
                ).run()
                assert _result_sig(res) == ref, (
                    f"diverged at pool={pool} graph={backend} shards={shards}"
                )
                if backend == "disk":
                    bgx.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_async_pipeline_first_order_identical(small_blocked):
    task = deepwalk_task(walks_per_vertex=2, length=10, seed=3)
    r_serial = BiBlockEngine(
        small_blocked, task, record_walks=True, async_pipeline=False
    ).run()
    r_async = BiBlockEngine(small_blocked, task, record_walks=True).run()
    assert _result_sig(r_async) == _result_sig(r_serial)


def test_async_pipeline_overlaps_and_reduces_stalls(small_blocked):
    """The gauges: async overlaps load bytes and stalls strictly fewer slots
    than the serial run executes; both runs agree on the walks.  The gauges
    are deterministic (enqueue order, not thread timing) — pin that too."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    r_async = BiBlockEngine(small_blocked, task, pool_flush_walks=64).run()
    r_serial = BiBlockEngine(
        small_blocked, task, async_pipeline=False, pool_flush_walks=64
    ).run()
    np.testing.assert_array_equal(r_async.endpoint_counts, r_serial.endpoint_counts)
    assert r_async.stats.overlapped_load_bytes > 0
    assert r_async.stats.time_slots == r_serial.stats.time_slots
    assert r_async.stats.pipeline_stall_slots < r_serial.stats.time_slots
    # serial mode: every slot's pool load sat on the critical path
    assert r_serial.stats.pipeline_stall_slots == r_serial.stats.time_slots
    assert r_async.stats.writer_queue_peak > 0
    r_again = BiBlockEngine(small_blocked, task, pool_flush_walks=64).run()
    assert r_again.stats.overlapped_load_bytes == r_async.stats.overlapped_load_bytes
    assert r_again.stats.pipeline_stall_slots == r_async.stats.pipeline_stall_slots


def test_sharded_pool_charges_invariant_across_shard_counts(small_blocked):
    """Walk-spill charges are not merely deterministic per shard count —
    they are *invariant* across shard counts (a block's op stream lands on
    exactly one shard in program order, so its spill points cannot move),
    and the per-shard breakdown partitions the total exactly."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    ref = None
    for shards in (1, 2, 4, 8):
        res = BiBlockEngine(
            small_blocked, task, pool_flush_walks=64, pool_shards=shards
        ).run()
        s = res.stats
        sig = (
            res.endpoint_counts.tobytes(),
            s.walk_bytes_written,
            s.walk_bytes_read,
            s.block_ios,
            s.block_bytes,
            s.ondemand_ios,
            s.ondemand_bytes,
        )
        if ref is None:
            ref = sig
        assert sig == ref, f"diverged at pool_shards={shards}"
        if shards > 1:
            assert sum(s.shard_spill_bytes.values()) == s.walk_bytes_written
            assert len(s.shard_spill_bytes) >= 2, "spills never left one shard"


def test_writer_fault_leaves_no_orphaned_spill_dirs(small_blocked, tmp_path):
    """Satellite regression: a writer-thread fault aborting ``run()``
    mid-slot must remove the DiskWalkPool spill directories — including an
    explicitly-passed ``pool_dir`` the pool created (the whole makedirs
    chain, nested paths too) — not just the happy path's temp dir."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    for shards in (1, 4):
        # nested: every component below tmp_path is pool-created
        created_root = tmp_path / f"nested_{shards}"
        pool_dir = str(created_root / "deeper" / "pool")
        eng = BiBlockEngine(
            small_blocked,
            task,
            pool="disk",
            pool_flush_walks=0,
            pool_dir=pool_dir,
            pool_shards=shards,
        )
        assert os.path.isdir(pool_dir)

        def boom(b, batch, wid):
            raise RuntimeError("injected spill failure")

        if shards == 1:
            eng.pool.base._spill = boom
        else:
            for shard in eng.pool.shards:
                shard.base._spill = boom
        with pytest.raises(RuntimeError):
            eng.run()
        assert eng._closed
        assert not os.path.isdir(str(created_root)), (
            f"pool_shards={shards}: spill dir chain orphaned after a writer fault"
        )


# ---------------------------------------------------------------------------
# AsyncWalkPool: sequencing, tickets, faults, lifecycle
# ---------------------------------------------------------------------------

def _batch(rng, n, V=600):
    return WalkBatch(
        rng.integers(0, V, n), rng.integers(0, V, n),
        rng.integers(0, V, n), rng.integers(0, 100, n).astype(np.int32),
    )


def test_async_pool_preserves_serial_order_and_accounting():
    """Ticketed pushes + a FIFO drain reproduce the serial pool exactly:
    same walk order, same spill charges, prefix+remainder == one load."""
    rng = np.random.default_rng(0)
    batches = [_batch(rng, 7) for _ in range(6)]
    wids = [np.arange(7, dtype=np.int64) + 10 * k for k in range(6)]

    # push-order reference: one serial pool that sees all six pushes
    order_stats = IOStats()
    order_pool = MemoryWalkPool(2, order_stats, flush_walks=10)
    for b, w in zip(batches, wids):
        order_pool.push(0, b, w)
    ref_batch, ref_wid = order_pool.load(0)

    # accounting reference: a serial pool stepped through the SAME op
    # sequence the async pool will sequence (push x3, drain, push x3, drain)
    serial_stats = IOStats()
    serial = MemoryWalkPool(2, serial_stats, flush_walks=10)
    for b, w in zip(batches[:3], wids[:3]):
        serial.push(0, b, w)
    serial.load(0)
    for b, w in zip(batches[3:], wids[3:]):
        serial.push(0, b, w)
    serial.load(0)

    stats = IOStats()
    pool = AsyncWalkPool(MemoryWalkPool(2, stats, flush_walks=10), stats=stats)
    for b, w in zip(batches[:3], wids[:3]):
        pool.push(0, b, w)
    fut = pool.drain_async(0)  # prefix: exactly the first three pushes
    for b, w in zip(batches[3:], wids[3:]):
        pool.push(0, b, w)
    (pre_batch, pre_wid), n_pre, _spilled = fut.result()
    assert n_pre == 21
    rem_batch, rem_wid = pool.load(0)
    got = WalkBatch.concat([pre_batch, rem_batch])
    np.testing.assert_array_equal(got.cur, ref_batch.cur)
    np.testing.assert_array_equal(got.hop, ref_batch.hop)
    np.testing.assert_array_equal(np.concatenate([pre_wid, rem_wid]), ref_wid)
    # sequencing bookkeeping: every ticket applied, in order
    pool.barrier()
    assert pool.tickets_issued == 6 and pool.applied_ticket == 6
    assert stats.writer_queue_peak >= 1
    # spill accounting matches the serial pool stepped through the same op
    # sequence (same thresholds crossed at the same points)
    assert stats.walk_bytes_written == serial_stats.walk_bytes_written
    assert stats.walk_bytes_read == serial_stats.walk_bytes_read
    pool.close()


def test_async_pool_eager_counts_match_sequential_view():
    stats = IOStats()
    pool = AsyncWalkPool(MemoryWalkPool(3, stats), stats=stats)
    rng = np.random.default_rng(1)
    pool.push(1, _batch(rng, 5), np.arange(5, dtype=np.int64))
    assert pool.counts[1] == 5  # visible before the writer applied it
    fut = pool.drain_async(1)
    assert pool.counts[1] == 0  # drained at the enqueue point
    pool.push(1, _batch(rng, 2), np.arange(2, dtype=np.int64))
    assert pool.counts[1] == 2  # post-drain pushes reaccumulate
    assert fut.result()[1] == 5
    pool.close()


def test_writer_fault_propagates_out_of_run_and_close_does_not_hang(small_blocked):
    """Satellite pin: an exception in the persist worker must propagate out
    of ``run()``, and the engine teardown must complete."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    # flush_walks=0 spills on every push, so the fault fires immediately
    eng = BiBlockEngine(small_blocked, task, pool_flush_walks=0)
    assert isinstance(eng.pool, AsyncWalkPool)

    def boom(b, batch, wid):
        raise RuntimeError("injected spill failure")

    eng.pool.base._spill = boom
    with pytest.raises(RuntimeError):
        eng.run()
    # run()'s finally already closed the engine; close again is idempotent
    # and must not hang on the dead writer
    t = threading.Thread(target=eng.close)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "close() hung after a writer fault"
    assert eng.pool._error is not None


def test_async_pool_operations_raise_after_fault():
    stats = IOStats()
    pool = AsyncWalkPool(MemoryWalkPool(2, stats, flush_walks=0), stats=stats)

    def boom(b, batch, wid):
        raise RuntimeError("boom")

    pool.base._spill = boom
    rng = np.random.default_rng(2)
    pool.push(0, _batch(rng, 3), np.arange(3, dtype=np.int64))
    with pytest.raises(RuntimeError):
        pool.barrier()
    with pytest.raises(RuntimeError):
        pool.push(0, _batch(rng, 3), np.arange(3, dtype=np.int64))
    pool.close()
    pool.close()  # idempotent


# ---------------------------------------------------------------------------
# TimeSlotPlan / BucketCursor mechanics
# ---------------------------------------------------------------------------

def test_time_slot_plan_orders():
    p2 = TimeSlotPlan(6, order=2)
    assert list(p2.slots()) == [0, 1, 2, 3, 4]  # last block never owns a pool
    assert list(p2.ancillary_after(2)) == [3, 4, 5]
    p1 = TimeSlotPlan(6, order=1)
    assert list(p1.slots()) == [0, 1, 2, 3, 4, 5]


def test_time_slot_plan_next_slot_wraps():
    plan = TimeSlotPlan(5, order=2)  # slots 0..3
    pending = {2}
    assert plan.next_slot(0, lambda b: b in pending) == 2
    assert plan.next_slot(2, lambda b: b in pending) == 2  # wraps to itself
    assert plan.next_slot(3, lambda b: b in pending) == 2  # next superstep
    assert plan.next_slot(0, lambda b: False) is None


def test_bucket_cursor_matches_sorted_rescan_with_extensions():
    """The ordered cursor pops what ``sorted(pending)`` would, including
    ids merged in mid-iteration (buckets only grow, targets only later)."""
    rng = np.random.default_rng(3)
    cur = BucketCursor()
    for i in (4, 2, 7):
        cur.add(i, _batch(rng, 2), np.arange(2, dtype=np.int64))
    assert len(cur) == 3 and 4 in cur
    i1, b1, w1 = cur.pop()
    assert i1 == 2 and cur.peek() == 4
    # extension grows an existing bucket and creates a new later one
    cur.add(4, _batch(rng, 3), np.arange(3, dtype=np.int64))
    cur.add(5, _batch(rng, 1), np.zeros(1, np.int64))
    i2, b2, w2 = cur.pop()
    assert i2 == 4 and len(b2) == 5  # merged in push order
    assert [cur.pop()[0], cur.pop()[0]] == [5, 7]
    assert cur.pop() is None and cur.peek() is None
