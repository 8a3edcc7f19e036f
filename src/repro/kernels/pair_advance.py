"""Fused Pallas multi-hop advance over the packed ragged view pair.

This is Alg. 2's ``UpdateWalk`` loop — the compute hot-spot of the bi-block
engine — as **one** kernel invocation instead of a chain of XLA ops: the
vids-remap binary search, alias/uniform proposal, second-order rejection
with binary-search membership, termination/decay draw, and trace-record
packing all execute per walker tile with the view pair pinned in VMEM.

Layout is the :class:`~repro.engines.base.ResidentPair` packing — flat
ragged ``vids``/``indptr``/``indices`` segments plus per-slot base offsets
— *not* the contiguous ``(start, nverts)`` block pair the retired
single-step kernel assumed, so compacted on-demand views run as-is.

ThunderRW's step interleaving maps onto the grid: the walk batch streams
through in ``WALK_TILE`` chunks (grid dim 0) and each tile runs its *own*
multi-hop ``while_loop``, masking per lane.  A lane that leaves the pair or
terminates stops contributing (its ``resident`` bit drops) without
serializing the lanes still walking; a tile whose lanes have all stalled
exits its loop immediately.  Per grid step the VMEM working set is

    (SV + SP + SE) * 4 bytes          (vids + indptr + indices)
  + 2 * SE * 4 (+ SE * 4)             (alias tables when weighted)
  + WALK_TILE * (7 * 4 + trace cols)  (walker lanes + trace tile)

which for the default ``WALK_TILE = 512`` leaves the paper's "block size"
knob (ME ~ 400-500 K edges on a 16 MB VMEM part) intact.

Every draw goes through :mod:`repro.kernels.rng` — the hand-rolled
threefry2x32 keyed ``(base_key, walk_id, hop, round)`` — so the fused path
reproduces :func:`repro.engines.step.pair_advance_impl` (and therefore the
in-memory oracle) bit for bit; ``advance_impl={"jax","pallas"}`` in
:class:`repro.engines.base.EngineBase` switches between them.

The kernel runs only under the Pallas interpreter (``interpret=True``,
which CPU CI exercises).  Mosaic refuses to lower it: the binary searches
and row lookups index 1-D refs with vector indices (``flat[mid]`` in
:func:`_lower_bound`), and Mosaic supports only 2-D gathers.  The engines
therefore admit ``advance_impl="pallas"`` on the CPU backend alone, and
``tests/test_tpu_compile.py`` pins the refusal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import rng

__all__ = ["WALK_TILE", "fused_advance_pair", "pair_advance_kernel"]

#: walker lanes per grid step
WALK_TILE = 512


def _lower_bound(flat, lo, hi, z, *, n_iters: int):
    """Kernel twin of :func:`repro.engines.step.lower_bound_rows`: fixed
    ``n_iters``-halving lower bound of ``z`` in sorted ``flat[lo:hi]``."""

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) // 2
        val = flat[jnp.clip(mid, 0, flat.shape[0] - 1)]
        valid = lo_ < hi_
        go_right = valid & (val < z)
        lo_ = jnp.where(go_right, mid + 1, lo_)
        hi_ = jnp.where(valid & ~go_right, mid, hi_)
        return lo_, hi_

    lo_f, _ = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    pos = jnp.clip(lo_f, 0, flat.shape[0] - 1)
    return lo_f, (lo_f < hi) & (flat[pos] == z)


def pair_advance_kernel(
    vids_ref,      # [SV] i32   VMEM, whole pair resident
    nverts_ref,    # [2] i32    scalars (VMEM for interpret, SMEM-ish)
    vid_base_ref,  # [2] i32
    ptr_base_ref,  # [2] i32
    ind_base_ref,  # [2] i32
    indptr_ref,    # [SP] i32
    indices_ref,   # [SE] i32
    alias_j_ref,   # [SE] i32 ([1] dummy if not has_alias)
    alias_q_ref,   # [SE] f32
    key_ref,       # [2] u32    the task base key's raw halves
    ilen_ref,      # [1] i32    walk length in edges
    fpar_ref,      # [3] f32    (decay, p, q)
    wid_ref,       # [T] i32    walker tile (grid dim 0)
    prev_ref,      # [T] i32
    cur_ref,       # [T] i32
    hop_ref,       # [T] i32
    alive_ref,     # [T] i32
    prev_out,      # [T] i32
    cur_out,       # [T] i32
    hop_out,       # [T] i32
    alive_out,     # [T] i32
    trace_out,     # [T, max_len+2] i32 ([T, 1] if not record)
    iters_out,     # [T] i32    the tile's loop iterations, on every lane
    *,
    order: int,
    k_max: int,
    n_iters: int,
    v_iters: int,
    record: bool,
    has_alias: bool,
    max_len: int,
    max_hops: int,
):
    T = prev_ref.shape[0]
    vids = vids_ref[...]
    indptr = indptr_ref[...]
    indices = indices_ref[...]
    vb0, vb1 = vid_base_ref[0], vid_base_ref[1]
    nv0, nv1 = nverts_ref[0], nverts_ref[1]
    pb0, pb1 = ptr_base_ref[0], ptr_base_ref[1]
    ib0, ib1 = ind_base_ref[0], ind_base_ref[1]
    length = ilen_ref[0]
    decay, p, q = fpar_ref[0], fpar_ref[1], fpar_ref[2]
    max_bias = jnp.maximum(1.0, jnp.maximum(1.0 / p, 1.0 / q))

    wid = wid_ref[...]
    prev0 = prev_ref[...]
    cur0 = cur_ref[...]
    hop0 = hop_ref[...]
    alive0 = alive_ref[...] > 0
    # per-walk streams, hoisted: the hop/round folds happen inside the loop
    kwid = rng.fold_in(key_ref[0], key_ref[1], wid)
    trace0 = jnp.full(trace_out.shape, -1, jnp.int32)

    def locate(v):
        r0, found0 = _lower_bound(
            vids, jnp.full((T,), vb0), jnp.full((T,), vb0 + nv0), v, n_iters=v_iters
        )
        r1, found1 = _lower_bound(
            vids, jnp.full((T,), vb1), jnp.full((T,), vb1 + nv1), v, n_iters=v_iters
        )
        slot = jnp.where(found0, 0, 1).astype(jnp.int32)
        row = jnp.where(found0, r0 - vb0, r1 - vb1)
        row = jnp.maximum(row, 0)
        return slot, row, found0 | found1

    def cond(state):
        _, _, _, _, resident, _, _, _, it = state
        return jnp.any(resident) & (it < max_hops)

    def body(state):
        prev_, cur_, hop_, alive_, resident, slot, row, trace_, it = state
        kw0, kw1 = rng.fold_in(kwid[0], kwid[1], hop_)

        movable = resident
        pslot = jnp.where(slot == 0, pb0, pb1)
        row_start = indptr[pslot + row]
        deg = indptr[pslot + row + 1] - row_start
        dead = movable & (deg <= 0)
        movable = movable & (deg > 0)
        deg_c = jnp.maximum(deg, 1)
        islot = jnp.where(slot == 0, ib0, ib1)

        if order == 2:
            uslot, urow, _ = locate(prev_)
            pu = jnp.where(uslot == 0, pb0, pb1)
            u_start = indptr[pu + urow]
            ulo = jnp.where(uslot == 0, ib0, ib1) + u_start
            uhi = ulo + (indptr[pu + urow + 1] - u_start)

        # ---- proposal + rejection, k_max rounds unrolled --------------------
        z = cur_
        accepted = ~movable
        for kk in range(k_max):
            u1, u2, u3 = rng.uniform3(*rng.fold_in(kw0, kw1, kk))
            kloc = jnp.minimum((u1 * deg_c).astype(jnp.int32), deg_c - 1)
            idx = islot + row_start + kloc
            if has_alias:
                take_alias = u2 >= alias_q_ref[...][idx]
                kloc = jnp.where(take_alias, alias_j_ref[...][idx], kloc)
                idx = islot + row_start + kloc
            zk = indices[idx]
            if order == 2:
                _, memb = _lower_bound(indices, ulo, uhi, zk, n_iters=n_iters)
                bias = jnp.where(zk == prev_, 1.0 / p, jnp.where(memb, 1.0, 1.0 / q))
                acc_p = bias / max_bias
                acc_p = jnp.where(hop_ == 0, 1.0, acc_p)  # first step: 1st-order
            else:
                acc_p = jnp.ones((T,), jnp.float32)
            last = kk == k_max - 1
            take = (~accepted) & movable & ((u3 < acc_p) | last)
            z = jnp.where(take, zk, z)
            accepted = accepted | take

        # ---- commit ---------------------------------------------------------
        u_term = rng.uniform1(*rng.fold_in(kw0, kw1, k_max))
        new_hop = hop_ + movable.astype(jnp.int32)
        new_prev = jnp.where(movable, cur_, prev_)
        new_cur = jnp.where(movable, z, cur_)
        finished = movable & (new_hop >= length)
        stopped = movable & (u_term >= decay)
        new_alive = alive_ & ~dead & ~finished & ~stopped
        new_slot, new_row, new_found = locate(new_cur)
        new_resident = new_alive & new_found
        if record:
            # one-hot column select — the Mosaic-friendly spelling of the
            # impl's scatter trace_.at[iota, cols].set(new_cur); the dump
            # column max_len+1 absorbs writes of frozen lanes
            cols = jnp.where(movable, jnp.clip(new_hop, 0, max_len), max_len + 1)
            onehot = jax.lax.broadcasted_iota(jnp.int32, trace_.shape, 1) == cols[:, None]
            trace_ = jnp.where(onehot, new_cur[:, None], trace_)
        return (
            new_prev,
            new_cur,
            new_hop,
            new_alive,
            new_resident,
            new_slot,
            new_row,
            trace_,
            it + 1,
        )

    slot0, row0, found0 = locate(cur0)
    resident0 = alive0 & found0
    init = (prev0, cur0, hop0, alive0, resident0, slot0, row0, trace0, jnp.int32(0))
    prev_f, cur_f, hop_f, alive_f, _, _, _, trace_f, it_f = jax.lax.while_loop(cond, body, init)

    prev_out[...] = prev_f
    cur_out[...] = cur_f
    hop_out[...] = hop_f
    alive_out[...] = alive_f.astype(jnp.int32)
    trace_out[...] = trace_f
    iters_out[...] = jnp.full((T,), it_f, jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "order",
        "k_max",
        "n_iters",
        "v_iters",
        "record",
        "has_alias",
        "max_len",
        "max_hops",
        "interpret",
        "walk_tile",
    ),
)
def fused_advance_pair(
    vids,
    nverts,
    vid_base,
    indptr,
    ptr_base,
    indices,
    ind_base,
    alias_j,
    alias_q,
    wid,
    prev,
    cur,
    hop,
    alive,
    key,
    length,
    decay,
    p,
    q,
    *,
    order: int,
    k_max: int,
    n_iters: int,
    v_iters: int,
    record: bool,
    has_alias: bool,
    max_len: int,
    interpret: bool,
    max_hops: int | None = None,
    walk_tile: int = WALK_TILE,
):
    """Drop-in fused replacement for :func:`repro.engines.step.advance_pair`.

    Identical argument list and return contract
    ``(prev, cur, hop, alive, steps, trace, lane_iters)``; bit-identical
    walk outputs.  ``lane_iters`` counts this kernel's own work: every tile
    runs all its lanes until its last walk leaves, so it is the tile width
    times the tile's iterations, summed over tiles.  The
    extra statics select the Pallas lowering: ``interpret`` (the Pallas
    interpreter vs Mosaic, which refuses this kernel), ``walk_tile`` (grid chunk), and
    ``max_hops`` (loop bound — ``None`` means the full ``max_len + 1``
    sweep; 1 gives the single-step form :mod:`repro.kernels.ops` exposes).
    """
    n0 = prev.shape[0]
    tile = min(walk_tile, n0)
    pad = (-n0) % tile

    def pad_lane(x, fill):
        return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)]) if pad else x

    wid = pad_lane(wid, 0)
    prev = pad_lane(prev, 0)
    cur = pad_lane(cur, 0)
    hop_in = pad_lane(hop, 0)
    alive_i = pad_lane(alive.astype(jnp.int32), 0)
    N = prev.shape[0]
    grid = (N // tile,)
    hops = (max_len + 1) if max_hops is None else max_hops
    TC = (max_len + 2) if record else 1

    k0, k1 = rng.key_halves(key)
    keypair = jnp.stack([k0, k1]).astype(jnp.uint32)
    ilen = jnp.asarray(length, jnp.int32).reshape(1)
    fpar = jnp.stack([decay, p, q]).astype(jnp.float32)

    pair_spec = lambda s: pl.BlockSpec(s, lambda i: (0,) * len(s))
    walk_spec = pl.BlockSpec((tile,), lambda i: (i,))
    trace_spec = pl.BlockSpec((tile, TC), lambda i: (i, 0))

    kern = functools.partial(
        pair_advance_kernel,
        order=order,
        k_max=k_max,
        n_iters=n_iters,
        v_iters=v_iters,
        record=record,
        has_alias=has_alias,
        max_len=max_len,
        max_hops=hops,
    )
    prev_f, cur_f, hop_f, alive_f, trace, iters = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pair_spec(vids.shape),
            pair_spec((2,)),
            pair_spec((2,)),
            pair_spec((2,)),
            pair_spec((2,)),
            pair_spec(indptr.shape),
            pair_spec(indices.shape),
            pair_spec(alias_j.shape),
            pair_spec(alias_q.shape),
            pair_spec((2,)),
            pair_spec((1,)),
            pair_spec((3,)),
            walk_spec,
            walk_spec,
            walk_spec,
            walk_spec,
            walk_spec,
        ],
        out_specs=[walk_spec, walk_spec, walk_spec, walk_spec, trace_spec, walk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N, TC), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
        ],
        interpret=interpret,
    )(
        vids,
        nverts,
        vid_base,
        ptr_base,
        ind_base,
        indptr,
        indices,
        alias_j,
        alias_q,
        keypair,
        ilen,
        fpar,
        wid,
        prev,
        cur,
        hop_in,
        alive_i,
    )
    # hop only advances on committed moves, so the delta *is* the step count
    steps = jnp.sum(hop_f - hop_in).astype(jnp.int32)
    # each lane holds its tile's iterations: the padded lanes ran too
    lane_iters = jnp.sum(iters).astype(jnp.int32)
    if record:
        trace = trace[:n0, : max_len + 1]
    else:
        trace = jnp.full((1, 1), -1, jnp.int32)
    return prev_f[:n0], cur_f[:n0], hop_f[:n0], alive_f[:n0] > 0, steps, trace, lane_iters
