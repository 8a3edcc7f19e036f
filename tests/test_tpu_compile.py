"""The advance compiles for a TPU v5e chip at real block sizes.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology, which finds what the interpreter and the CPU backend
cannot (Mosaic refusals, programs that do not fit device memory).  The
shapes are the full-view caps of the Kronecker scale-20 graph in 8
edge-balanced blocks (what ``chip_smoke.py`` runs), hard-coded rather than
generated, with batches of 4,096 and 16,384 walks (the advance runs in
three stages of narrowing width at both).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engines.step import advance_pair, remap_search_iters, stage_widths
from repro.kernels.pair_advance import fused_advance_pair

#: largest block of the Kronecker scale-20 graph in 8 edge-balanced blocks
BLOCK_VERTS = 450_491
BLOCK_EDGES = 3_925_872
#: batch widths: the benchmark's calls pad to 8,192, its set-up's to 16,384
WALKS = (4096, 16384)
LENGTH = 80
#: device memory of one v5e chip
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot describe a v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _pair_args(sharding, has_alias: bool, walks: int = WALKS[0]):
    """Shapes of ``ResidentPair.device_args()`` with two full views."""

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    alias_n = 2 * BLOCK_EDGES if has_alias else 1
    pair = (
        s((2 * BLOCK_VERTS,), jnp.int32),  # vids
        s((2,), jnp.int32),  # nverts
        s((2,), jnp.int32),  # vid_base
        s((2 * (BLOCK_VERTS + 1),), jnp.int32),  # indptr
        s((2,), jnp.int32),  # ptr_base
        s((2 * BLOCK_EDGES,), jnp.int32),  # indices
        s((2,), jnp.int32),  # ind_base
        s((alias_n,), jnp.int32),  # alias_j
        s((alias_n,), jnp.float32),  # alias_q
    )
    walks = (
        s((walks,), jnp.int32),  # wid
        s((walks,), jnp.int32),  # prev
        s((walks,), jnp.int32),  # cur
        s((walks,), jnp.int32),  # hop
        s((walks,), jnp.bool_),  # alive
        s((2,), jnp.uint32),  # key
        s((), jnp.int32),  # length
        s((), jnp.float32),  # decay
        s((), jnp.float32),  # p
        s((), jnp.float32),  # q
    )
    return pair + walks


def _statics(order: int, has_alias: bool, record: bool):
    return dict(
        order=order,
        k_max=16 if order == 2 else 1,
        n_iters=int(np.ceil(np.log2(BLOCK_EDGES))) + 2,
        v_iters=remap_search_iters(BLOCK_VERTS),
        record=record,
        has_alias=has_alias,
        max_len=LENGTH,
    )


@pytest.mark.parametrize("record", [False, True], ids=["norecord", "record"])
@pytest.mark.parametrize("has_alias", [False, True], ids=["uniform", "alias"])
@pytest.mark.parametrize("order", [1, 2])
@pytest.mark.parametrize("walks", WALKS)
def test_advance_pair_compiles_for_v5e(one_chip, no_compile_cache, walks, order, has_alias, record):
    assert len(stage_widths(walks)) == 3
    args = _pair_args(one_chip, has_alias, walks)
    compiled = advance_pair.lower(*args, **_statics(order, has_alias, record)).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 8 * BLOCK_EDGES  # the pair is really there
    assert total < V5E_HBM_BYTES, f"{total} bytes do not fit one v5e chip"


def test_fused_kernel_is_refused_by_mosaic(one_chip, no_compile_cache):
    """Mosaic lowers only 2-D gathers; the kernel's binary searches gather
    from 1-D refs.  The PR that rewrites the kernel for Mosaic flips this."""
    args = _pair_args(one_chip, False)
    with pytest.raises(NotImplementedError, match="gather"):
        fused_advance_pair.lower(*args, **_statics(2, False, False), interpret=False).compile()
