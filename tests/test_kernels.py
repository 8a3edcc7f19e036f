"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/param sweeps.

The fused advance kernel is validated two independent ways: single-hop
against the dense ``node2vec_step_ref`` oracle fed explicit counter-keyed
uniforms, and multi-hop against the plain jitted ``pair_advance_impl`` —
both bitwise.  The staged jitted advance is checked against itself run
one stage wide, on the same walks cut into chunks.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from repro.testing import given, settings, st

from repro.core import erdos_renyi, partition_into_n_blocks
from repro.core.graph import BlockView
from repro.engines.base import ResidentPair
from repro.engines.step import advance_pair, stage_widths
from repro.kernels import (
    alias_step,
    bucket_hist_kernel,
    bucket_hist_ref,
    fused_advance_pair,
    node2vec_step,
    node2vec_step_ref,
    rng,
)


def _pair_args(n_verts=500, n_edges=3500, nb=4, b0=0, b1=2, weighted=False, seed=1):
    g = erdos_renyi(n_verts, n_edges, seed=seed)
    if weighted:
        r = np.random.default_rng(seed)
        from repro.core import CSRGraph

        g = CSRGraph(g.indptr, g.indices,
                     (r.random(g.num_edges) + 0.1).astype(np.float32))
    bg = partition_into_n_blocks(g, nb)
    if weighted:
        bg.ensure_alias()
    rp = ResidentPair(bg, has_alias=weighted)
    rp.set_slot(0, BlockView.from_resident(bg.materialize_block(b0)))
    rp.set_slot(1, BlockView.from_resident(bg.materialize_block(b1)))
    pair, v_iters = rp.device_args()
    return bg, pair, v_iters


def _counter_unif(key, wid, hop, k_max):
    """The engine's draw schedule, materialized: (key, wid, hop, round)."""
    kw0, kw1 = rng.fold_in(*rng.fold_in(*rng.key_halves(key), wid), hop)
    return jnp.stack(
        [jnp.stack(rng.uniform3(*rng.fold_in(kw0, kw1, kk)), axis=-1)
         for kk in range(k_max)],
        axis=1,
    )


@pytest.mark.parametrize("p,q", [(1.0, 1.0), (4.0, 0.25), (0.25, 4.0)])
@pytest.mark.parametrize("n_walks", [256, 1024])
def test_fused_single_hop_matches_dense_ref(p, q, n_walks):
    bg, pair, v_iters = _pair_args()
    r = np.random.default_rng(0)
    cur = jnp.asarray(r.integers(bg.block_starts[0], bg.block_starts[1], n_walks).astype(np.int32))
    prev = jnp.asarray(r.integers(bg.block_starts[2], bg.block_starts[3], n_walks).astype(np.int32))
    hop = jnp.asarray(r.integers(0, 6, n_walks).astype(np.int32))
    active = jnp.asarray(r.random(n_walks) < 0.9)
    wid = jnp.asarray(r.integers(0, 1 << 20, n_walks).astype(np.int32))
    key = jax.random.PRNGKey(7)
    kw = dict(p=p, q=q, k_max=4, n_iters=16, v_iters=v_iters)
    zk, mk = node2vec_step(*pair, wid, prev, cur, hop, active, key,
                           use_kernel=True, interpret=True, walk_tile=256, **kw)
    unif = _counter_unif(key, wid, hop, 4)
    zr, mr = node2vec_step_ref(*pair, prev, cur, hop, active, unif,
                               p=p, q=q, k_max=4)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))


def test_fused_kernel_weighted_alias_path():
    bg, pair, v_iters = _pair_args(weighted=True)
    r = np.random.default_rng(3)
    n = 512
    cur = jnp.asarray(r.integers(bg.block_starts[0], bg.block_starts[1], n).astype(np.int32))
    prev = jnp.asarray(r.integers(bg.block_starts[2], bg.block_starts[3], n).astype(np.int32))
    wid = jnp.arange(n, dtype=jnp.int32)
    hop = jnp.ones(n, jnp.int32)
    active = jnp.ones(n, bool)
    key = jax.random.PRNGKey(1)
    kw = dict(p=0.5, q=2.0, k_max=2, n_iters=16, v_iters=v_iters, has_alias=True)
    zk, mk = node2vec_step(*pair, wid, prev, cur, hop, active, key,
                           use_kernel=True, interpret=True, **kw)
    unif = _counter_unif(key, wid, hop, 2)
    zr, mr = node2vec_step_ref(*pair, prev, cur, hop, active, unif,
                               p=0.5, q=2.0, k_max=2, has_alias=True)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))


def test_fused_multi_hop_matches_jax_impl():
    """The tentpole equality: whole multi-hop advance, kernel vs plain jit."""
    bg, pair, v_iters = _pair_args(b0=0, b1=1)
    r = np.random.default_rng(5)
    n = 384  # not a multiple of the tile — exercises lane padding
    cur = jnp.asarray(r.integers(bg.block_starts[0], bg.block_starts[2], n).astype(np.int32))
    prev = jnp.asarray(r.integers(bg.block_starts[0], bg.block_starts[2], n).astype(np.int32))
    hop = jnp.asarray(r.integers(0, 4, n).astype(np.int32))
    alive = jnp.asarray(r.random(n) < 0.95)
    wid = jnp.asarray(r.integers(0, 1 << 20, n).astype(np.int32))
    key = jax.random.PRNGKey(11)
    sc = (jnp.int32(10), jnp.float32(0.9), jnp.float32(4.0), jnp.float32(0.25))
    kw = dict(order=2, k_max=8, n_iters=16, v_iters=v_iters,
              record=True, has_alias=False, max_len=10)
    ref = advance_pair(*pair, wid, prev, cur, hop, alive, key, *sc, **kw)
    fus = fused_advance_pair(*pair, wid, prev, cur, hop, alive, key, *sc, **kw,
                             interpret=True, walk_tile=256)
    # the walk outputs; the seventh, lane_iters, counts each kernel's own work
    for a, b in zip(ref[:6], fus[:6]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the staged advance: compacting resident lanes changes no walk
# ---------------------------------------------------------------------------

def _batch(bg, n, *, blocks, seed, alive_share=0.95):
    """``n`` walks with cur and prev in ``blocks`` (a range of block ids)."""
    r = np.random.default_rng(seed)
    lo, hi = bg.block_starts[blocks[0]], bg.block_starts[blocks[-1] + 1]
    cur = r.integers(lo, hi, n).astype(np.int32)
    prev = r.integers(lo, hi, n).astype(np.int32)
    hop = r.integers(0, 3, n).astype(np.int32)
    # retired walks first: the last lane, which the fill lanes of a compacted
    # stage copy, is a live walk
    alive = np.sort(r.random(n) < alive_share)
    wid = r.permutation(1 << 20)[:n].astype(np.int32)
    return tuple(jnp.asarray(x) for x in (wid, prev, cur, hop, alive))


def _chunked(pair, walks, key, sc, kw, chunk=256):
    """The reference: each chunk of ``chunk`` walks alone through
    ``advance_pair``, a single stage, joined back into one batch."""
    assert stage_widths(chunk) == (chunk,)
    n = walks[0].shape[0]
    outs = [
        advance_pair(*pair, *(w[s : s + chunk] for w in walks), key, *sc, **kw)
        for s in range(0, n, chunk)
    ]
    joined = [np.concatenate([np.asarray(o[i]) for o in outs]) for i in range(4)]
    steps = sum(int(o[4]) for o in outs)
    trace = np.concatenate([np.asarray(o[5]) for o in outs]) if kw["record"] else None
    return joined, steps, trace


#: (walks, order, alias, record, alive share, blocks in the graph): the pair
#: is blocks 0 and 1; at three blocks it holds two thirds of the graph
STAGED_CASES = {
    "n4096-o2-record": (4096, 2, False, True, 0.95, 4),
    "n4096-o1-alias": (4096, 1, True, False, 0.95, 4),
    "n16384-o2-alias-record": (16384, 2, True, True, 0.95, 4),
    "n16384-o1": (16384, 1, False, False, 0.95, 4),
    "n4096-mostly-retired": (4096, 2, False, True, 0.05, 4),
    "n4096-resident-for-tens": (4096, 2, False, True, 0.95, 3),
}


@pytest.mark.parametrize("case", sorted(STAGED_CASES))
def test_staged_advance_matches_single_stage_chunks(case):
    n, order, alias, record, alive_share, nb = STAGED_CASES[case]
    assert len(stage_widths(n)) == 3
    bg, pair, v_iters = _pair_args(n_verts=1200, n_edges=9000, nb=nb, b0=0, b1=1,
                                   weighted=alias)
    walks = _batch(bg, n, blocks=(0, 1), seed=n + nb, alive_share=alive_share)
    key = jax.random.PRNGKey(5)
    sc = (jnp.int32(60), jnp.float32(1.0 if nb == 3 else 0.95),
          jnp.float32(4.0), jnp.float32(0.25))
    kw = dict(order=order, k_max=8 if order == 2 else 1, n_iters=16, v_iters=v_iters,
              record=record, has_alias=alias, max_len=60)
    out = advance_pair(*pair, *walks, key, *sc, **kw)
    joined, steps, trace = _chunked(pair, walks, key, sc, kw)
    for a, b in zip(out[:4], joined):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert int(out[4]) == steps > 0
    if record:
        np.testing.assert_array_equal(np.asarray(out[5]), trace)
    moved = np.asarray(out[2]) - np.asarray(walks[3])
    if nb == 3:
        assert moved.max() >= 10  # some walks stay resident for tens of hops
    # the narrower stages ran: fewer lanes than the full width each iteration
    assert int(out[6]) < n * (moved.max() + 1)


def test_staged_advance_matches_the_fused_kernel():
    bg, pair, v_iters = _pair_args(n_verts=1200, n_edges=9000, b0=0, b1=1)
    walks = _batch(bg, 1024, blocks=(0, 1), seed=9)
    assert stage_widths(1024) == (1024, 256)
    key = jax.random.PRNGKey(13)
    sc = (jnp.int32(20), jnp.float32(0.95), jnp.float32(4.0), jnp.float32(0.25))
    kw = dict(order=2, k_max=8, n_iters=16, v_iters=v_iters,
              record=True, has_alias=False, max_len=20)
    ref = advance_pair(*pair, *walks, key, *sc, **kw)
    fus = fused_advance_pair(*pair, *walks, key, *sc, **kw, interpret=True)
    for a, b in zip(ref[:6], fus[:6]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("batch", ["all-leave-after-one-hop", "none-resident"])
def test_lane_iters(batch):
    bg, pair, v_iters = _pair_args(n_verts=1200, n_edges=9000, b0=0, b1=1)
    n = 4096
    wid, prev, cur, hop, alive = _batch(bg, n, blocks=(0, 1), seed=3)
    deg = np.diff(bg.graph.indptr)
    starts = np.asarray(cur)
    # every walk starts at a vertex it can leave: one hop of length 1 ends it
    cur = jnp.asarray(np.where(deg[starts] > 0, starts, prev))
    assert (deg[np.asarray(cur)] > 0).all()
    alive = jnp.full(n, batch == "all-leave-after-one-hop")
    key = jax.random.PRNGKey(0)
    sc = (jnp.int32(1), jnp.float32(1.0), jnp.float32(4.0), jnp.float32(0.25))
    kw = dict(order=2, k_max=8, n_iters=16, v_iters=v_iters,
              record=False, has_alias=False, max_len=1)
    out = advance_pair(*pair, wid, prev, cur, jnp.zeros(n, jnp.int32), alive, key, *sc, **kw)
    if batch == "none-resident":
        assert int(out[4]) == int(out[6]) == 0
    else:
        assert int(out[4]) == n
        assert int(out[6]) == n


def test_ops_wrapper_pads_and_dispatches():
    bg, pair, v_iters = _pair_args()
    r = np.random.default_rng(0)
    n = 300  # not a multiple of the tile
    cur = jnp.asarray(r.integers(bg.block_starts[0], bg.block_starts[1], n).astype(np.int32))
    prev = cur
    wid = jnp.arange(n, dtype=jnp.int32)
    hop = jnp.zeros(n, jnp.int32)
    active = jnp.ones(n, bool)
    k = jax.random.PRNGKey(0)
    zk, mk = node2vec_step(*pair, wid, prev, cur, hop, active, k,
                           v_iters=v_iters, use_kernel=True,
                           interpret=True, walk_tile=256)
    zr, mr = node2vec_step(*pair, wid, prev, cur, hop, active, k,
                           v_iters=v_iters, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    assert zk.shape == (n,)
    # sampled vertices are real neighbors of cur
    g = bg.graph
    zs = np.asarray(zk)
    for i in range(0, n, 29):
        if mk[i]:
            assert zs[i] in g.neighbors(int(cur[i]))


def test_alias_step_first_order():
    bg, pair, v_iters = _pair_args()
    r = np.random.default_rng(0)
    n = 256
    cur = jnp.asarray(
        r.integers(bg.block_starts[0], bg.block_starts[1], n).astype(np.int32)
    )
    wid = jnp.arange(n, dtype=jnp.int32)
    z, moved = alias_step(*pair, wid, cur, jnp.ones(n, bool), jax.random.PRNGKey(2),
                          v_iters=v_iters, has_alias=False,
                          interpret=True, walk_tile=256)
    g = bg.graph
    zs = np.asarray(z)
    for i in range(0, n, 17):
        assert zs[i] in g.neighbors(int(cur[i]))


@given(
    n=st.sampled_from([1024, 2048]),
    nb=st.integers(2, 9),
    seed=st.integers(0, 100),
)
@settings(max_examples=10, deadline=None)
def test_bucket_hist_property(n, nb, seed):
    r = np.random.default_rng(seed)
    ids = jnp.asarray(r.integers(0, nb, n).astype(np.int32))
    valid = jnp.asarray(r.random(n) < 0.7)
    hk = bucket_hist_kernel(ids, valid, num_buckets=nb, interpret=True)
    hr = bucket_hist_ref(ids, valid, num_buckets=nb)
    np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))
    assert int(hk.sum()) == int(valid.sum())
