"""The BlockView layer: activated-subgraph execution correctness pins.

The strongest property in the system: walks are a pure function of
``(task seed, walk id)`` — independent of the loading method, graph backend,
walk-pool backend, bucket scheduling, and even of whether the whole graph is
resident (the in-memory oracle).  These tests pin it, plus the footprint
win (``peak_resident_bytes``) and the engine lifecycle fixes (close on
raise, idempotent close, uniform ``loader_summary``).
"""

import os

import numpy as np
import pytest

from repro.core import (
    BiBlockEngine,
    BlockView,
    CSRGraph,
    InMemoryWalker,
    PlainBucketEngine,
    erdos_renyi,
    partition_into_n_blocks,
    rwnv_task,
)
from repro.core.transition import Node2vec, WalkTask
from repro.testing import given, settings, st


def _result_sig(res):
    return (
        res.endpoint_counts.tobytes(),
        None if res.corpus is None else res.corpus.tobytes(),
        res.stats.steps_sampled,
    )


# ---------------------------------------------------------------------------
# Property: loading methods and backends never change the walks
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    nv=st.integers(60, 140),
    nblocks=st.integers(2, 5),
    weighted=st.booleans(),
)
def test_loading_modes_bit_identical(seed, nv, nblocks, weighted):
    """full / ondemand / auto x ram / disk graph: identical endpoint
    histograms and corpora on random small graphs."""
    import tempfile

    from repro.io import DiskBlockedGraph, write_block_file

    g = erdos_renyi(nv, nv * 5, seed=seed)
    if weighted:
        rng = np.random.default_rng(seed)
        g = CSRGraph(
            g.indptr, g.indices,
            (rng.random(g.num_edges) * 2 + 0.25).astype(np.float32),
        )
    bg = partition_into_n_blocks(g, nblocks)
    path = os.path.join(
        tempfile.mkdtemp(prefix="grasorw_bv_"),
        f"g_{seed}_{nv}_{nblocks}_{int(weighted)}.grb",
    )
    write_block_file(bg, path)
    task = rwnv_task(p=3.0, q=0.5, walks_per_vertex=1, length=6, seed=seed)
    ref = None
    for loading in ("full", "ondemand", "auto"):
        for backend in ("ram", "disk"):
            bgx = bg if backend == "ram" else DiskBlockedGraph(path)
            res = BiBlockEngine(bgx, task, loading=loading, record_walks=True).run()
            sig = _result_sig(res)
            if ref is None:
                ref = sig
            assert sig == ref, f"diverged at loading={loading} graph={backend}"
            if backend == "disk":
                bgx.close()
    os.remove(path)


def test_engines_match_inmemory_oracle_bitwise(small_blocked):
    """Counter-based RNG: out-of-core engines sample the *same walks* as the
    whole-graph oracle, not merely the same distribution.  SOGW/SGSC
    qualify too since their paid-for prev adjacencies are pinned as a
    gathered view (the membership probe runs on the true rows)."""
    from repro.core import SOGWEngine

    task = rwnv_task(p=2.0, q=0.5, walks_per_vertex=2, length=10, seed=11)
    oracle = InMemoryWalker(small_blocked, task).run()
    engines = [
        BiBlockEngine(small_blocked, task, record_walks=True),
        PlainBucketEngine(small_blocked, task, record_walks=True),
        SOGWEngine(small_blocked, task, record_walks=True),
        SOGWEngine(small_blocked, task, static_cache=True, record_walks=True),
    ]
    for eng in engines:
        res = eng.run()
        np.testing.assert_array_equal(res.endpoint_counts, oracle.endpoint_counts)
        np.testing.assert_array_equal(res.corpus, oracle.corpus)
        assert res.stats.steps_sampled == oracle.stats.steps_sampled


def test_ondemand_restart_task_identical(small_blocked):
    """Decay termination draws are (walk, hop)-keyed too."""
    task = WalkTask(
        Node2vec(p=2.0, q=0.5), length=15,
        query_vertex=3, total_walks=256, decay=0.85, seed=4,
    )
    r_full = BiBlockEngine(small_blocked, task, loading="full").run()
    r_od = BiBlockEngine(small_blocked, task, loading="ondemand").run()
    np.testing.assert_array_equal(r_full.endpoint_counts, r_od.endpoint_counts)
    assert r_od.stats.ondemand_ios > 0


# ---------------------------------------------------------------------------
# The footprint win and the view mechanics
# ---------------------------------------------------------------------------

def test_ondemand_peak_resident_bytes_lower():
    """Sparse buckets on a skewed graph: activated views shrink the resident
    peak (the bench's ondemand_exec acceptance, at test scale)."""
    from repro.core import barabasi_albert

    g = barabasi_albert(1500, 8, seed=3)
    bg = partition_into_n_blocks(g, 8)
    task = WalkTask(
        Node2vec(p=2.0, q=0.5), length=20,
        query_vertex=5, total_walks=256, decay=0.85, seed=9,
    )
    r_full = BiBlockEngine(bg, task, loading="full").run()
    r_od = BiBlockEngine(bg, task, loading="ondemand").run()
    np.testing.assert_array_equal(r_full.endpoint_counts, r_od.endpoint_counts)
    assert 0 < r_od.stats.peak_resident_bytes < r_full.stats.peak_resident_bytes


def test_partial_view_rows_match_full(small_blocked):
    """An activated view's rows are bit-identical to the full block's."""
    b = 1
    full = BlockView.from_resident(small_blocked.materialize_block(b))
    s = int(small_blocked.block_starts[b])
    rng = np.random.default_rng(0)
    verts = rng.choice(
        np.arange(s, int(small_blocked.block_starts[b + 1])), 17, replace=False
    )
    part = small_blocked.partial_view(b, verts)
    assert part.kind == "activated" and full.kind == "full"
    np.testing.assert_array_equal(part.vids, np.unique(verts))
    for k, v in enumerate(part.vids):
        np.testing.assert_array_equal(part.row(k), full.row(int(v) - s))
    assert part.nbytes() < full.nbytes()


def test_view_extension_appends_rows(small_blocked):
    b = 0
    s, e = int(small_blocked.block_starts[b]), int(small_blocked.block_starts[b + 1])
    base = small_blocked.partial_view(b, np.arange(s, s + 5))
    ext = small_blocked.partial_view(b, np.arange(s + 8, s + 11))
    merged = base.extended(ext)
    assert merged.nverts == 8
    np.testing.assert_array_equal(
        merged.vids, np.concatenate([np.arange(s, s + 5), np.arange(s + 8, s + 11)])
    )
    full = BlockView.from_resident(small_blocked.materialize_block(b))
    for k, v in enumerate(merged.vids):
        np.testing.assert_array_equal(merged.row(k), full.row(int(v) - s))
    with pytest.raises(ValueError):
        merged.extended(small_blocked.partial_view(b + 1, np.arange(e, e + 2)))


def _merge_row_by_row(a, b):
    """Reference merge: each row of either view, one by one, in vid order."""
    rows = sorted(
        [(int(v), a, k) for k, v in enumerate(a.vids)] + [(int(v), b, k) for k, v in enumerate(b.vids)],
        key=lambda r: r[0],
    )
    names = ["indices"] + (["alias_j", "alias_q"] if a.alias_j is not None else [])
    segs = {name: [] for name in names}
    indptr = [0]
    for _, src, k in rows:
        lo, hi = src.indptr[k], src.indptr[k + 1]
        for name in names:
            segs[name].append(getattr(src, name)[lo:hi])
        indptr.append(indptr[-1] + hi - lo)
    dtypes = {"indices": np.int32, "alias_j": np.int32, "alias_q": np.float32}
    out = {"vids": np.array([v for v, _, _ in rows], np.int32), "indptr": np.array(indptr, np.int32)}
    for name in names:
        out[name] = np.concatenate(segs[name] or [np.zeros(0)]).astype(dtypes[name])
    return out


@pytest.mark.parametrize("case", ["interleaved", "empty_other", "empty_self", "alias"])
def test_view_merge_equals_row_by_row_reference(small_graph, case):
    """``extended``'s array merge gives the reference row-by-row merge bit
    for bit (dtypes included), on random disjoint interleaved vid sets."""
    rng = np.random.default_rng(9)
    g = small_graph
    if case == "alias":
        g = CSRGraph(g.indptr, g.indices, rng.uniform(0.5, 2.0, g.num_edges).astype(np.float32))
    bg = partition_into_n_blocks(g, 3)
    if case == "alias":
        bg.ensure_alias()
    b = 1
    s, e = int(bg.block_starts[b]), int(bg.block_starts[b + 1])
    vs = rng.choice(np.arange(s, e), (e - s) // 2, replace=False)
    mine = rng.random(vs.size) < 0.7
    if case == "empty_other":
        mine[:] = True
    elif case == "empty_self":
        mine[:] = False
    a, o = bg.partial_view(b, vs[mine]), bg.partial_view(b, vs[~mine])
    got = a.extended(o)
    want = _merge_row_by_row(a, o)
    assert got.kind == "activated" and got.block_id == b
    assert (got.alias_j is None) == (case != "alias")
    for name, arr in want.items():
        assert getattr(got, name).dtype == arr.dtype, name
        np.testing.assert_array_equal(getattr(got, name), arr, err_msg=name)


def test_blockstore_partial_prefetch_subset_served(small_blocked):
    """A prefetched partial view is served as a base when the request grew
    (buckets only gain walks) and never changes the served vertex set."""
    from repro.core import IOStats
    from repro.io import BlockStore

    stats = IOStats()
    store = BlockStore(small_blocked, stats)
    s = int(small_blocked.block_starts[2])
    store.prefetch_partial(2, np.arange(s, s + 6))
    view = store.partial_view(2, np.arange(s, s + 10))  # grew by 4
    assert store.partial_prefetch_hits == 1
    np.testing.assert_array_equal(view.vids, np.arange(s, s + 10))
    # a non-subset prefetch is discarded, never served
    store.prefetch_partial(2, np.arange(s + 20, s + 24))
    view2 = store.partial_view(2, np.arange(s, s + 3))
    assert store.partial_builds == 1
    np.testing.assert_array_equal(view2.vids, np.arange(s, s + 3))
    store.close()


# ---------------------------------------------------------------------------
# Engine lifecycle: close on raise, idempotent close, uniform loader_summary
# ---------------------------------------------------------------------------

def test_run_closes_storage_on_raise(small_blocked, tmp_path, monkeypatch):
    """A run that raises still releases the prefetch thread and the disk
    pool's spill dir (regression: close() was skipped when run() raised)."""
    task = rwnv_task(walks_per_vertex=1, length=8, seed=0)
    eng = BiBlockEngine(
        small_blocked, task, pool="disk", pool_flush_walks=0,
    )
    pool_dir = eng.pool.directory
    assert os.path.isdir(pool_dir)

    def boom(*a, **kw):
        raise RuntimeError("injected advance failure")

    monkeypatch.setattr(eng, "_advance", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.run()
    assert eng._closed
    assert not os.path.isdir(pool_dir), "disk pool spill dir leaked"
    assert eng.blocks._executor is None, "prefetch executor leaked"
    # close() is idempotent — result() after run() double-closes safely
    eng.close()
    eng.close()


def test_engine_context_manager(small_blocked):
    task = rwnv_task(walks_per_vertex=1, length=6, seed=0)
    with BiBlockEngine(small_blocked, task) as eng:
        res = eng.run()
    assert eng._closed
    assert res.endpoint_counts.sum() == res.num_walks


def test_loader_summary_uniform_across_engines(small_blocked):
    """result() reports loader_summary uniformly: a dict for the LBL engine,
    None for baselines and the oracle — never a missing attribute."""
    from repro.core import SOGWEngine

    task = rwnv_task(walks_per_vertex=1, length=6, seed=0)
    r_bb = BiBlockEngine(small_blocked, task).run()
    assert isinstance(r_bb.loader_summary, dict)
    assert "full_samples" in r_bb.loader_summary
    for Engine in (PlainBucketEngine, SOGWEngine):
        res = Engine(small_blocked, task).run()
        assert res.loader_summary is None
    assert InMemoryWalker(small_blocked, task).run().loader_summary is None
