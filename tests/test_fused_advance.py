"""The fused Pallas advance (interpret mode) is observationally identical
to the plain jitted JAX advance and to the in-memory oracle.

``advance_impl`` only swaps the lowering of ``UpdateWalk``; every walk,
every endpoint, every step count, and every deterministic I/O charge must
be bit-identical across {full, ondemand} loading x {ram, disk} graph x
{memory, disk} pool, serially and under the async pipeline with sharded
pools.  Any divergence means the kernel's RNG or sampling logic forked
from the engine impl.
"""

import os
import shutil
import tempfile

import numpy as np
import pytest

from repro.core import (
    BiBlockEngine,
    erdos_renyi,
    partition_into_n_blocks,
    rwnv_task,
)
from repro.engines.inmemory import InMemoryWalker
from repro.testing import given, settings, st


def _sig(res):
    return (
        res.endpoint_counts.tobytes(),
        None if res.corpus is None else res.corpus.tobytes(),
        res.stats.steps_sampled,
        res.stats.block_ios,
        res.stats.block_bytes,
        res.stats.ondemand_ios,
        res.stats.ondemand_bytes,
    )


@given(
    seed=st.integers(0, 10_000),
    nv=st.integers(50, 100),
    nblocks=st.integers(2, 4),
    shards=st.sampled_from([1, 4]),
)
@settings(max_examples=2, deadline=None)
def test_fused_advance_matrix_bitwise(seed, nv, nblocks, shards):
    """pallas == jax == oracle across loading x graph x pool, and under the
    async pipeline with pool_shards in {1, 4}."""
    from repro.io import DiskBlockedGraph, write_block_file

    g = erdos_renyi(nv, nv * 5, seed=seed)
    bg = partition_into_n_blocks(g, nblocks)
    task = rwnv_task(p=3.0, q=0.5, walks_per_vertex=1, length=6, seed=seed)
    oracle = InMemoryWalker(bg, task).run(record_walks=True)
    tmp = tempfile.mkdtemp(prefix="grasorw_fused_")
    try:
        path = os.path.join(tmp, "g.grb")
        write_block_file(bg, path)
        for loading in ("full", "ondemand"):
            for backend in ("ram", "disk"):
                for pool in ("memory", "disk"):
                    sigs = {}
                    for impl in ("jax", "pallas"):
                        bgx = bg if backend == "ram" else DiskBlockedGraph(path)
                        res = BiBlockEngine(
                            bgx,
                            task,
                            record_walks=True,
                            async_pipeline=False,
                            loading=loading,
                            pool=pool,
                            pool_dir=os.path.join(
                                tmp, f"p_{loading}_{backend}_{pool}_{impl}"
                            ),
                            advance_impl=impl,
                        ).run()
                        sigs[impl] = _sig(res)
                        # both impls reproduce the oracle walks bitwise
                        np.testing.assert_array_equal(
                            res.endpoint_counts, oracle.endpoint_counts
                        )
                        np.testing.assert_array_equal(res.corpus, oracle.corpus)
                        if backend == "disk":
                            bgx.close()
                    # ... and charge identical deterministic I/O
                    assert sigs["pallas"] == sigs["jax"], (
                        f"diverged at loading={loading} graph={backend} pool={pool}"
                    )
        # the async pipeline with sharded pools rides the same kernel
        r_async = BiBlockEngine(
            bg,
            task,
            record_walks=True,
            async_pipeline=True,
            pool="disk",
            pool_shards=shards,
            pool_dir=os.path.join(tmp, f"p_async_{shards}"),
            advance_impl="pallas",
        ).run()
        np.testing.assert_array_equal(r_async.endpoint_counts, oracle.endpoint_counts)
        np.testing.assert_array_equal(r_async.corpus, oracle.corpus)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_advance_impl_validated():
    bg = partition_into_n_blocks(erdos_renyi(40, 160, seed=0), 2)
    task = rwnv_task(walks_per_vertex=1, length=4, seed=0)
    with pytest.raises(ValueError, match="advance_impl"):
        BiBlockEngine(bg, task, advance_impl="mosaic")


def test_pallas_refused_off_the_cpu(monkeypatch):
    """Off the CPU the kernel would need Mosaic, which refuses its 1-D
    gathers: construction fails instead of timing the interpreter."""
    import repro.engines.base as base

    bg = partition_into_n_blocks(erdos_renyi(40, 160, seed=0), 2)
    task = rwnv_task(walks_per_vertex=1, length=4, seed=0)
    monkeypatch.setattr(base.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="gather"):
        BiBlockEngine(bg, task, advance_impl="pallas")
    BiBlockEngine(bg, task).close()  # the jax lowering is admitted everywhere


def test_fused_advance_first_order(small_blocked):
    """DeepWalk (order-1, k_max=1) path through the fused kernel."""
    from repro.core import deepwalk_task

    task = deepwalk_task(walks_per_vertex=1, length=8, seed=2)
    r_jax = BiBlockEngine(small_blocked, task, record_walks=True,
                          async_pipeline=False).run()
    r_pal = BiBlockEngine(small_blocked, task, record_walks=True,
                          async_pipeline=False, advance_impl="pallas").run()
    assert _sig(r_jax) == _sig(r_pal)
