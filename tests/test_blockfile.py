"""On-disk block container (repro.io.blockfile).

Pins the PR's acceptance criteria: a writer→reader roundtrip reproduces the
in-RAM backend bit-for-bit (header, padding, alias tables); on-demand
partial reads return the same rows as full loads and move exactly the bytes
the paper's accounting charges; corrupt/truncated files fail loudly; and
every out-of-core engine is bit-identical and charge-identical across the
RAM and disk graph backends, for both full-load and on-demand loading.
"""

import os
import tempfile

import numpy as np
import pytest

from repro.core import (
    BiBlockEngine,
    CSRGraph,
    PlainBucketEngine,
    SOGWEngine,
    erdos_renyi,
    partition_into_n_blocks,
    rwnv_task,
)
from repro.io import (
    BLOCK_FILE_NAME,
    BlockFileError,
    BlockStore,
    DiskBlockedGraph,
    model_ondemand_io,
    plan_reads,
    write_and_open,
    write_block_file,
)
from repro.testing import given, settings, st


@pytest.fixture(scope="module")
def disk_graph(small_blocked, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("blockfile") / BLOCK_FILE_NAME)
    write_block_file(small_blocked, path)
    return path


@pytest.fixture()
def weighted_blocked(small_graph):
    rng = np.random.default_rng(17)
    g = CSRGraph(
        small_graph.indptr, small_graph.indices,
        rng.uniform(0.5, 2.0, small_graph.num_edges).astype(np.float32),
    )
    return partition_into_n_blocks(g, 5)


# ---------------------------------------------------------------------------
# writer -> reader roundtrip
# ---------------------------------------------------------------------------

def test_header_and_metadata_roundtrip(small_blocked, disk_graph):
    with DiskBlockedGraph(disk_graph) as dg:
        assert dg.num_vertices == small_blocked.num_vertices
        assert dg.num_edges == small_blocked.num_edges
        assert dg.num_blocks == small_blocked.num_blocks
        assert dg.max_block_verts == small_blocked.max_block_verts
        assert dg.max_block_edges == small_blocked.max_block_edges
        assert not dg.has_weights
        np.testing.assert_array_equal(dg.block_starts, small_blocked.block_starts)
        np.testing.assert_array_equal(dg.block_nverts, small_blocked.block_nverts)
        np.testing.assert_array_equal(dg.block_nedges, small_blocked.block_nedges)
        np.testing.assert_array_equal(dg.degrees, small_blocked.degrees)
        d_ram = small_blocked.describe()
        d_dsk = dg.describe()
        assert d_ram == d_dsk


def test_blocks_bit_identical_including_padding(small_blocked, disk_graph):
    with DiskBlockedGraph(disk_graph) as dg:
        for b in range(small_blocked.num_blocks):
            ram = small_blocked.materialize_block(b)
            dsk = dg.materialize_block(b)
            assert (dsk.block_id, dsk.start, dsk.nverts, dsk.nedges) == (
                ram.block_id, ram.start, ram.nverts, ram.nedges)
            # padded arrays identical, including the fill values
            np.testing.assert_array_equal(dsk.indptr, ram.indptr)
            np.testing.assert_array_equal(dsk.indices, ram.indices)
            assert dsk.nbytes_full() == ram.nbytes_full()


def test_full_load_bytes_match_fd_reads(small_blocked, disk_graph):
    """The headline property: nbytes_full == bytes read from the fd."""
    with DiskBlockedGraph(disk_graph) as dg:
        total = 0
        for b in range(dg.num_blocks):
            blk = dg.materialize_block(b)
            total += blk.nbytes_full()
        assert dg.data_bytes_read == total
        assert dg.full_loads == dg.num_blocks
        assert dg.aux_bytes_read == 0  # unweighted: no aux arrays on disk


def test_weighted_roundtrip_with_alias_tables(weighted_blocked, tmp_path):
    path = str(tmp_path / BLOCK_FILE_NAME)
    info = write_block_file(weighted_blocked, path)
    assert info["file_bytes"] == os.path.getsize(path)
    weighted_blocked.ensure_alias()
    with DiskBlockedGraph(path) as dg:
        assert dg.has_weights
        dg.ensure_alias()  # present: no-op
        for b in range(dg.num_blocks):
            ram = weighted_blocked.materialize_block(b)
            dsk = dg.materialize_block(b)
            np.testing.assert_array_equal(dsk.alias_j, ram.alias_j)
            np.testing.assert_array_equal(dsk.alias_q, ram.alias_q)
        assert dg.aux_bytes_read == 12 * dg.num_edges


def test_read_csr_reconstruction(small_blocked, disk_graph, weighted_blocked, tmp_path):
    with DiskBlockedGraph(disk_graph) as dg:
        g2 = dg.read_csr()
    g = small_blocked.graph
    np.testing.assert_array_equal(g2.indptr, g.indptr)
    np.testing.assert_array_equal(g2.indices, g.indices)
    assert g2.weights is None
    wpath = str(tmp_path / BLOCK_FILE_NAME)
    write_block_file(weighted_blocked, wpath)
    with DiskBlockedGraph(wpath) as dw:
        gw = dw.read_csr()
    np.testing.assert_array_equal(gw.weights, weighted_blocked.graph.weights)


def test_edge_cut_matches_ram_backend(small_blocked, disk_graph):
    with DiskBlockedGraph(disk_graph) as dg:
        assert dg.edge_cut() == pytest.approx(small_blocked.edge_cut())


# ---------------------------------------------------------------------------
# on-demand partial reads
# ---------------------------------------------------------------------------

def test_ondemand_rows_match_full_load(small_blocked, disk_graph):
    rng = np.random.default_rng(2)
    with DiskBlockedGraph(disk_graph) as dg:
        for b in (0, 2, 4):
            s, e = int(dg.block_starts[b]), int(dg.block_starts[b + 1])
            verts = rng.integers(s, e, size=7)
            rows = dg.read_rows(b, verts)
            full = small_blocked.materialize_block(b)
            for v, seg in rows.items():
                lv = v - s
                rs, re = int(full.indptr[lv]), int(full.indptr[lv + 1])
                np.testing.assert_array_equal(seg, full.indices[rs:re])


def test_ondemand_bytes_match_activated_accounting(small_blocked, disk_graph):
    """read_rows moves exactly activated_load_bytes() bytes through the fd."""
    rng = np.random.default_rng(3)
    with DiskBlockedGraph(disk_graph) as dg:
        s, e = int(dg.block_starts[1]), int(dg.block_starts[2])
        verts = rng.integers(s, e, size=12)  # duplicates dedupe like the charge
        dg.read_rows(1, verts)
        assert dg.ondemand_bytes_read == dg.activated_load_bytes(verts)
        assert dg.activated_load_bytes(verts) == small_blocked.activated_load_bytes(verts)
        assert dg.data_bytes_read == 0  # no full load happened


def test_partial_block_is_activated_view(small_blocked, disk_graph):
    with DiskBlockedGraph(disk_graph) as dg:
        b = 3
        s = int(dg.block_starts[b])
        verts = [s, s + 2, s + 5]
        part = dg.partial_block(b, verts)
        full = small_blocked.materialize_block(b)
        assert part.indptr.shape == full.indptr.shape
        assert part.indices.shape == full.indices.shape
        for lv in range(int(dg.block_nverts[b])):
            seg = part.indices[part.indptr[lv] : part.indptr[lv + 1]]
            if s + lv in verts:
                ref = full.indices[full.indptr[lv] : full.indptr[lv + 1]]
                np.testing.assert_array_equal(seg, ref)
            else:
                assert seg.size == 0  # unrequested rows stay empty


def test_read_rows_rejects_foreign_vertices(disk_graph):
    with DiskBlockedGraph(disk_graph) as dg:
        outside = int(dg.block_starts[2]) + 1  # lives in block 2, not 0
        with pytest.raises(IndexError):
            dg.read_rows(0, [outside])


def _vertex_set(name, s, e, rng):
    return {
        "empty": np.zeros(0, np.int64),
        "one": np.array([s + (e - s) // 2]),
        "block": np.arange(s, e),
        "subset": rng.integers(s, e, size=(e - s) // 3),  # duplicates dedupe
    }[name]


@pytest.mark.parametrize("vset", ["empty", "one", "block", "subset"])
@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
def test_flat_reader_matches_ram_view_and_reads(
    small_blocked, weighted_blocked, tmp_path, monkeypatch, weighted, vset
):
    """The gap-0 partial view is the in-RAM view bit for bit, read as the
    per-vertex reference reads: index pair then row per vertex in vid order
    (then alias_j, alias_q per vertex), 2 (4 weighted) syscalls a vertex,
    exactly activated_load_bytes of index + CSR."""
    bg = weighted_blocked if weighted else small_blocked
    if weighted:
        bg.ensure_alias()
    path = str(tmp_path / BLOCK_FILE_NAME)
    write_block_file(bg, path)
    b = 2
    s, e = int(bg.block_starts[b]), int(bg.block_starts[b + 1])
    verts = _vertex_set(vset, s, e, np.random.default_rng(4))
    reads = []
    pread, preadv = os.pread, os.preadv
    monkeypatch.setattr(os, "pread", lambda fd, n, off: reads.append((off, n)) or pread(fd, n, off))
    monkeypatch.setattr(
        os, "preadv", lambda fd, bufs, off: reads.append((off, len(bufs[0]))) or preadv(fd, bufs, off)
    )
    with DiskBlockedGraph(path) as dg:
        base = int(dg.block_offsets[b])
        reads.clear()
        got = dg.partial_view(b, verts)
        want = bg.partial_view(b, verts)
        for name in ("vids", "indptr", "indices", "alias_j", "alias_q"):
            g, w = getattr(got, name), getattr(want, name)
            if not weighted and name.startswith("alias"):
                assert g is None and w is None
                continue
            assert g.dtype == w.dtype, name
            np.testing.assert_array_equal(g, w, err_msg=name)
        assert got.kind == "activated" and got.block_id == b

        vs = np.unique(verts)
        nv, ne = int(bg.block_nverts[b]), int(bg.block_nedges[b])
        rs = bg.graph.indptr[vs] - bg.graph.indptr[s]
        deg = bg.graph.degrees[vs]
        csr = base + 4 * (nv + 1)
        expect = []
        for v, r, d in zip(vs.tolist(), rs.tolist(), deg.tolist()):
            expect += [(base + 4 * (v - s), 8), (csr + 4 * r, 4 * d)]
        if weighted:
            for r, d in zip(rs.tolist(), deg.tolist()):
                expect += [(csr + 8 * ne + 4 * r, 4 * d), (csr + 12 * ne + 4 * r, 4 * d)]
        assert reads == expect
        assert dg.ondemand_syscalls == (4 if weighted else 2) * vs.size
        assert dg.ondemand_bytes_read == dg.activated_load_bytes(verts)
        assert dg.aux_bytes_read == (8 * int(deg.sum()) if weighted else 0)
        assert dg.ondemand_reads == (1 if vs.size else 0)


@pytest.mark.parametrize("gap", [0, 4096], ids=["per-vertex", "planner"])
def test_index_pair_disagreeing_with_degrees_names_the_vertex(
    small_blocked, disk_graph, tmp_path, gap
):
    """An index pair that disagrees with the resident degree table fails
    the on-demand read, naming the vertex, instead of sizing a wrong view."""
    path, _ = _copy(disk_graph, tmp_path, "bad_pair.grb")
    b = 1
    s = int(small_blocked.block_starts[b])
    v = s + 3
    with DiskBlockedGraph(path) as dg:
        entry = int(dg.block_offsets[b]) + 4 * (v - s + 1)  # v's row end
    with open(path, "r+b") as f:
        f.seek(entry)
        end = int(np.frombuffer(f.read(4), np.int32)[0])
        f.seek(entry)
        f.write(np.int32(end + 1).tobytes())
    with DiskBlockedGraph(path, io_coalesce_gap=gap) as dg:
        with pytest.raises(BlockFileError, match=f"v={v}\\b"):
            dg.partial_view(b, [s, v, s + 9])


# ---------------------------------------------------------------------------
# corruption / truncation error paths
# ---------------------------------------------------------------------------

def _copy(path, tmp_path, name):
    dst = str(tmp_path / name)
    with open(path, "rb") as f:
        raw = f.read()
    with open(dst, "wb") as f:
        f.write(raw)
    return dst, raw


def test_bad_magic_rejected(disk_graph, tmp_path):
    dst, raw = _copy(disk_graph, tmp_path, "bad_magic.grb")
    with open(dst, "r+b") as f:
        f.write(b"NOTAGRSW")
    with pytest.raises(BlockFileError, match="magic"):
        DiskBlockedGraph(dst)


def test_bad_version_rejected(disk_graph, tmp_path):
    dst, raw = _copy(disk_graph, tmp_path, "bad_version.grb")
    with open(dst, "r+b") as f:
        f.seek(8)
        f.write((99).to_bytes(4, "little"))
    with pytest.raises(BlockFileError, match="version"):
        DiskBlockedGraph(dst)


def test_truncated_file_rejected_at_open(disk_graph, tmp_path):
    dst, raw = _copy(disk_graph, tmp_path, "trunc.grb")
    with open(dst, "r+b") as f:
        f.truncate(len(raw) - 128)
    with pytest.raises(BlockFileError, match="truncated|size"):
        DiskBlockedGraph(dst)


def test_truncated_header_rejected(disk_graph, tmp_path):
    dst, _ = _copy(disk_graph, tmp_path, "header.grb")
    with open(dst, "r+b") as f:
        f.truncate(40)  # mid-header
    with pytest.raises(BlockFileError, match="truncated"):
        DiskBlockedGraph(dst)


def test_corrupt_block_maxima_rejected(disk_graph, tmp_path):
    dst, _ = _copy(disk_graph, tmp_path, "maxima.grb")
    with open(dst, "r+b") as f:
        f.seek(40)  # header max_block_verts field
        f.write((7).to_bytes(8, "little"))
    with pytest.raises(BlockFileError, match="maxima"):
        DiskBlockedGraph(dst)


def test_corrupt_offset_index_rejected(disk_graph, tmp_path):
    import struct

    from repro.io.blockfile import _HEADER

    dst, raw = _copy(disk_graph, tmp_path, "offsets.grb")
    nb = struct.unpack_from("<Q", raw, 16)[0]
    # first block_offsets entry lives right after header + block_starts
    off = _HEADER.size + 8 * (nb + 1)
    with open(dst, "r+b") as f:
        f.seek(off)
        f.write((12345).to_bytes(8, "little"))
    with pytest.raises(BlockFileError, match="offset index"):
        DiskBlockedGraph(dst)


def test_write_and_open_bootstrap(small_blocked, tmp_path):
    """The launcher/bench one-call path: write into a dir and open."""
    with write_and_open(small_blocked, str(tmp_path)) as dg:
        assert isinstance(dg, DiskBlockedGraph)
        assert dg.path == str(tmp_path / BLOCK_FILE_NAME)
        assert dg.num_edges == small_blocked.num_edges
    with write_and_open(small_blocked) as dg2:  # fresh temp dir
        assert os.path.exists(dg2.path)
        assert dg2.path != str(tmp_path / BLOCK_FILE_NAME)


def test_writer_cleans_up_temp_on_failure(small_blocked, tmp_path, monkeypatch):
    """An interrupted write leaves neither the target nor a stray temp."""
    import repro.io.blockfile as bf

    def boom(src, dst):
        raise RuntimeError("injected failure")

    monkeypatch.setattr(bf.os, "replace", boom)
    target = tmp_path / BLOCK_FILE_NAME
    with pytest.raises(RuntimeError, match="injected failure"):
        write_block_file(small_blocked, str(target))
    assert list(tmp_path.iterdir()) == []  # no target, no .tmp leftovers


# ---------------------------------------------------------------------------
# engines: bit-identical walks + identical deterministic I/O across backends
# ---------------------------------------------------------------------------

def _strip_wall_clock(stats):
    # writer_queue_peak is enqueue-time queue depth — timing-dependent by
    # design (docs/execution.md: "don't pin it"), so it is stripped
    # alongside the wall-clock timers before the strict equality check
    d = stats.as_dict()
    for k in ("exec_time", "sim_wall_time", "writer_queue_peak"):
        d.pop(k)
    return d


@pytest.mark.parametrize("loading", ["full", "ondemand", "auto"])
def test_biblock_bit_identical_ram_vs_disk(small_blocked, disk_graph, loading):
    """The acceptance criterion: BiBlockEngine on DiskBlockedGraph (full-load
    AND on-demand) == the in-RAM BlockedGraph, walks and counters."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    r_ram = BiBlockEngine(small_blocked, task, loading=loading).run()
    with DiskBlockedGraph(disk_graph) as dg:
        r_dsk = BiBlockEngine(dg, task, loading=loading).run()
        np.testing.assert_array_equal(r_ram.endpoint_counts, r_dsk.endpoint_counts)
        assert _strip_wall_clock(r_ram.stats) == _strip_wall_clock(r_dsk.stats)
        assert dg.data_bytes_read > 0  # the disk run really hit the fd


@pytest.mark.parametrize("Engine", [PlainBucketEngine, SOGWEngine])
def test_baseline_engines_bit_identical_ram_vs_disk(small_blocked, disk_graph, Engine):
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    r_ram = Engine(small_blocked, task).run()
    with DiskBlockedGraph(disk_graph) as dg:
        r_dsk = Engine(dg, task).run()
    np.testing.assert_array_equal(r_ram.endpoint_counts, r_dsk.endpoint_counts)
    assert _strip_wall_clock(r_ram.stats) == _strip_wall_clock(r_dsk.stats)


def test_weighted_biblock_bit_identical(weighted_blocked, tmp_path):
    path = str(tmp_path / BLOCK_FILE_NAME)
    write_block_file(weighted_blocked, path)
    task = rwnv_task(p=2.0, q=0.5, walks_per_vertex=1, length=8, seed=5)
    r_ram = BiBlockEngine(weighted_blocked, task).run()
    with DiskBlockedGraph(path) as dg:
        r_dsk = BiBlockEngine(dg, task).run()
    np.testing.assert_array_equal(r_ram.endpoint_counts, r_dsk.endpoint_counts)


# ---------------------------------------------------------------------------
# gap-aware read planner (repro.io.ioplan)
# ---------------------------------------------------------------------------

def test_empty_ondemand_read_not_counted(disk_graph):
    """Regression: a zero-vertex request issues no pread and counts nothing."""
    with DiskBlockedGraph(disk_graph) as dg:
        assert dg.read_rows(1, []) == {}
        assert dg.ondemand_reads == 0
        assert dg.ondemand_syscalls == 0
        assert dg.ondemand_bytes_read == 0
        view = dg.partial_view(1, [])
        assert view.nverts == 0
        assert dg.ondemand_reads == 0
        # a non-empty request still counts exactly one on-demand read
        dg.read_rows(1, [int(dg.block_starts[1])])
        assert dg.ondemand_reads == 1


@given(
    gap=st.sampled_from([0, 1, 64, 4096, 1 << 20]),
    seed=st.integers(0, 10_000),
    weighted=st.booleans(),
)
@settings(max_examples=20, deadline=None)
def test_planner_matches_per_vertex_reference(gap, seed, weighted):
    """Satellite property: for random graphs and random gap budgets the
    planner returns the same rows/alias segments and charges the same
    useful bytes as the per-vertex reference, with no more syscalls — and
    zero coalescing at ``gap_bytes=0``."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 120))
    g = erdos_renyi(n, int(rng.integers(n, 6 * n)), seed=seed)
    if weighted:
        g = CSRGraph(
            g.indptr, g.indices,
            rng.uniform(0.5, 2.0, g.num_edges).astype(np.float32),
        )
    bg = partition_into_n_blocks(g, int(rng.integers(2, 6)))
    if weighted:
        bg.ensure_alias()
    verts = rng.integers(0, n, size=int(rng.integers(1, 3 * n)))
    # tempfile instead of a pytest fixture: @given bodies cannot take
    # function-scoped fixtures (hypothesis health check / fallback shim)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, BLOCK_FILE_NAME)
        write_block_file(bg, path)
        _check_planner_vs_reference(path, verts, gap, weighted)


def _check_planner_vs_reference(path, verts, gap, weighted):
    with DiskBlockedGraph(path) as ref, DiskBlockedGraph(path, io_coalesce_gap=gap) as pln:
        v_ref = ref.gather_view(verts)
        v_pln = pln.gather_view(verts)
        np.testing.assert_array_equal(v_pln.vids, v_ref.vids)
        np.testing.assert_array_equal(v_pln.indptr, v_ref.indptr)
        np.testing.assert_array_equal(v_pln.indices, v_ref.indices)
        if weighted:
            np.testing.assert_array_equal(v_pln.alias_j, v_ref.alias_j)
            np.testing.assert_array_equal(v_pln.alias_q, v_ref.alias_q)
        assert pln.ondemand_bytes_read == ref.ondemand_bytes_read
        assert pln.ondemand_bytes_read == ref.activated_load_bytes(verts)
        assert pln.aux_bytes_read == ref.aux_bytes_read
        assert pln.ondemand_syscalls <= ref.ondemand_syscalls
        if gap == 0:
            # planner off: bit-for-bit the reference path
            assert pln.ondemand_syscalls == ref.ondemand_syscalls
            assert pln.coalesced_ranges == 0
            assert pln.coalesce_waste_bytes == 0
        # the pure model predicts the real executor exactly
        assert model_ondemand_io(ref, verts, gap) == (
            pln.ondemand_syscalls,
            pln.coalesced_ranges,
            pln.coalesce_waste_bytes,
        )


@given(seed=st.integers(0, 10_000), gap=st.integers(0, 500))
@settings(max_examples=25, deadline=None)
def test_plan_reads_moves_the_extent_union(seed, gap):
    """plan_reads covers every extent, never splits one, and its waste is
    exactly total-minus-union — 0 at gap 0."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 40))
    starts = np.sort(rng.integers(0, 2000, size=k))
    ends = starts + rng.integers(0, 60, size=k)
    plan = plan_reads(starts, ends, gap)
    union = np.zeros(int(ends.max()) + 1 if k else 0, bool)
    for s0, e0 in zip(starts, ends):
        union[s0:e0] = True
    covered = np.zeros_like(union)
    for s0, e0 in plan.ranges:
        covered[s0:e0] = True
    assert covered[union].all()  # every useful byte is read
    assert plan.useful_bytes == int(union.sum())
    assert plan.waste_bytes == plan.total_bytes - plan.useful_bytes
    if gap == 0:
        assert plan.waste_bytes == 0  # only overlap/adjacency merges
    for k_, (s0, e0) in enumerate(zip(starts, ends)):
        r = int(plan.seg_range[k_])
        if e0 == s0:
            assert r == -1  # empty extents read nothing
        else:
            assert plan.ranges[r, 0] <= s0 and e0 <= plan.ranges[r, 1]


@pytest.mark.parametrize("gap", [1, 4096, 1 << 20])
def test_coalesced_walks_and_charges_bit_identical(small_blocked, disk_graph, gap):
    """Engine gate: with the planner on, walks and every charged counter
    except the syscall/range/waste gauges (and the coalesce-aware modelled
    on-demand time) are identical to the gap-0 reference — on both
    backends — and the disk run's real planner counters equal the charged
    gauges when prefetch is off."""
    task = rwnv_task(walks_per_vertex=2, length=10, seed=7)
    ref = BiBlockEngine(small_blocked, task, loading="ondemand", prefetch=False).run()
    try:
        small_blocked.io_coalesce_gap = gap
        r_ram = BiBlockEngine(small_blocked, task, loading="ondemand", prefetch=False).run()
    finally:
        small_blocked.io_coalesce_gap = 0  # session-scoped fixture
    with DiskBlockedGraph(disk_graph, io_coalesce_gap=gap) as dg:
        r_dsk = BiBlockEngine(dg, task, loading="ondemand", prefetch=False).run()
        real = dg.counters()
    for r in (r_ram, r_dsk):
        np.testing.assert_array_equal(r.endpoint_counts, ref.endpoint_counts)
        assert r.stats.ondemand_bytes == ref.stats.ondemand_bytes
        assert r.stats.ondemand_ios == ref.stats.ondemand_ios
        assert r.stats.ondemand_syscalls < ref.stats.ondemand_syscalls
        assert r.stats.coalesced_ranges > 0
    # the planner is backend-invariant: ram and disk charge identically
    assert _strip_wall_clock(r_ram.stats) == _strip_wall_clock(r_dsk.stats)
    # honest accounting: with prefetch off the real preads equal the gauges
    assert real["ondemand_syscalls"] == r_dsk.stats.ondemand_syscalls
    assert real["coalesced_ranges"] == r_dsk.stats.coalesced_ranges
    assert real["coalesce_waste_bytes"] == r_dsk.stats.coalesce_waste_bytes
    assert real["ondemand_bytes_read"] == r_dsk.stats.ondemand_bytes


def test_schedule_batches_same_block_partials(small_blocked, disk_graph):
    """BlockStore.schedule unions same-slot partial requests per block into
    one prefetched build (one plan per block, not one per request)."""
    from repro.core import IOStats

    with DiskBlockedGraph(disk_graph) as dg:
        store = BlockStore(dg, IOStats(), capacity=2, enable_prefetch=True)
        s1 = int(dg.block_starts[1])
        store.schedule([
            ("partial", 1, np.array([s1, s1 + 2])),
            ("partial", 1, np.array([s1 + 1, s1 + 2])),
            ("full", 0),
        ])
        assert store.partial_prefetch_issued == 1
        view = store.partial_view(1, np.array([s1, s1 + 1, s1 + 2]))
        np.testing.assert_array_equal(view.vids, [s1, s1 + 1, s1 + 2])
        assert store.partial_prefetch_hits == 1  # the union served as base
        store.close()


def test_blockstore_lru_hides_rereads(small_blocked, disk_graph):
    """With a capacity-2 LRU the disk backend re-reads evicted blocks; the
    charged I/O stays deterministic while real reads track evictions."""
    from repro.core import IOStats

    with DiskBlockedGraph(disk_graph) as dg:
        stats = IOStats()
        store = BlockStore(dg, stats, capacity=2, enable_prefetch=False)
        store.get(0), store.get(0)  # second get served from LRU: one real read
        assert dg.full_loads == 1
        assert stats.block_ios == 2  # but both gets are charged (deterministic)
        store.get(1), store.get(2), store.get(0)  # 0 evicted -> re-read
        assert dg.full_loads == 4
        store.close()
