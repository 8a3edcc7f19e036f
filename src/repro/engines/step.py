"""The jitted pair-advance step shared by every engine.

Vectorised Alg. 2 ``UpdateWalk`` over a *view pair*: alias/uniform proposal +
Node2vec rejection test with binary-search membership
(:mod:`repro.core.sampling`); the fused Pallas kernel in
:mod:`repro.kernels.pair_advance` is the TPU version of exactly this loop.

Two properties distinguish this implementation from a textbook step loop:

* **Views, not blocks.**  The resident pair is two
  :class:`~repro.core.graph.BlockView`\\ s packed into flat ragged arrays —
  a *full* view (the whole block) or an *activated* view (a compacted CSR
  over only the bucket's activated vertices plus a remap table).  The kernel
  resolves a global vertex to its compact row by binary search over the
  view's sorted ``vids`` remap, so rejection sampling runs directly on the
  compacted arrays and the device footprint of an on-demand bucket is
  ``O(activated vertices)``.  A walk that reaches a vertex with no row in
  the pair simply stops being *resident* (it stays alive); the host engine
  either routes it (it left the block pair) or gathers its row and extends
  the view (a mid-advance extension).

* **Counter-based per-walk RNG.**  Every random draw is keyed by
  ``(base_key, walk_id, hop, round)`` via the hand-rolled threefry folds in
  :mod:`repro.kernels.rng` (bitwise ``jax.random.fold_in`` + ``uniform``) —
  never by call order.  A walk's trajectory is therefore a pure function of the task
  seed and its walk id, independent of batch composition, view shape,
  loading decisions, pause/resume, or which engine advances it.  This is
  what makes {full, ondemand, auto} loading x {ram, disk} graph x
  {memory, disk} pool — and the in-memory oracle — produce bit-identical
  walks.

``pair_advance_impl`` is the raw function (reused inside ``shard_map`` by
:mod:`repro.core.distributed`); ``advance_pair`` the jitted host entry point.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import rng

__all__ = [
    "VID_PAD",
    "advance_pair",
    "lower_bound_rows",
    "pair_advance_impl",
    "pow2_pad",
    "remap_search_iters",
]

#: vids padding value — sorts after every real vertex id
VID_PAD = jnp.iinfo(jnp.int32).max


def remap_search_iters(n: int) -> int:
    """Binary-search depth for a remap (``vids``) segment of ``n`` entries —
    the single source of the ``v_iters`` static the kernel consumes."""
    return int(np.ceil(np.log2(max(n, 2)))) + 1


def lower_bound_rows(flat, lo, hi, z, *, n_iters: int):
    """Batched lower bound of ``z`` within the sorted slice ``flat[lo:hi]``.

    Branch-free fixed-iteration binary search (``n_iters`` halvings, like
    :func:`repro.core.sampling.searchsorted_rows` but returning the
    insertion *position*).  Returns ``(pos, found)``.
    """
    lo0 = lo.astype(jnp.int32)
    hi0 = hi.astype(jnp.int32)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) // 2
        val = flat[jnp.clip(mid, 0, flat.shape[0] - 1)]
        valid = lo_ < hi_
        go_right = valid & (val < z)
        lo_ = jnp.where(go_right, mid + 1, lo_)
        hi_ = jnp.where(valid & ~go_right, mid, hi_)
        return lo_, hi_

    lo_f, _ = jax.lax.fori_loop(0, n_iters, body, (lo0, hi0))
    pos = jnp.clip(lo_f, 0, flat.shape[0] - 1)
    return lo_f, (lo_f < hi0) & (flat[pos] == z)


def pair_advance_impl(
    vids,        # [SV] i32 — both slots' sorted global vertex ids, concatenated
    nverts,      # [2] i32  — valid vids per slot
    vid_base,    # [2] i32  — offset of each slot's segment within vids
    indptr,      # [SP] i32 — concatenated compact local offsets
    ptr_base,    # [2] i32  — offset of each slot's indptr segment
    indices,     # [SE] i32 — concatenated global neighbor ids, sorted per row
    ind_base,    # [2] i32  — offset of each slot's indices segment
    alias_j,     # [SE] i32 — row-local alias slots (dummy if not has_alias)
    alias_q,     # [SE] f32
    wid,         # [N] i32  — walk ids (the per-walk RNG stream identity)
    prev,        # [N] i32
    cur,         # [N] i32
    hop,         # [N] i32
    alive,       # [N] bool — not yet terminated
    key,         # PRNG base key (task seed — NOT split per call)
    length,      # () i32 — walk length in edges
    decay,       # () f32 — per-step continue probability (1.0 = fixed len)
    p,           # () f32 — node2vec return parameter
    q,           # () f32 — node2vec in-out parameter
    *,
    order: int,
    k_max: int,
    n_iters: int,
    v_iters: int,
    record: bool,
    has_alias: bool,
    max_len: int,
):
    """Advance every walk until it leaves the resident view pair or
    terminates.  Returns ``(prev, cur, hop, alive, steps_taken, trace)``
    where ``trace[n, h]`` is the vertex walk n reached at hop h during this
    call (-1 = no move).
    """
    N = prev.shape[0]
    max_bias = jnp.maximum(1.0, jnp.maximum(1.0 / p, 1.0 / q))
    # per-walk streams: fold the walk id in once, the hop/round per draw —
    # all through the shared hand-rolled threefry (repro.kernels.rng), the
    # same primitive the fused Pallas kernel lowers under Mosaic
    kwid = rng.fold_in(*rng.key_halves(key), wid)
    # one spare "dump" column (max_len+1) absorbs writes of frozen walks
    trace0 = jnp.full((N, max_len + 2) if record else (1, 1), -1, dtype=jnp.int32)
    iota = jnp.arange(N)

    @jax.named_scope("advance.locate")
    def locate(v):
        """Resolve global vertex -> (slot, compact row, found) via the remap."""
        r0, found0 = lower_bound_rows(
            vids,
            jnp.full((N,), vid_base[0]),
            jnp.full((N,), vid_base[0] + nverts[0]),
            v,
            n_iters=v_iters,
        )
        r1, found1 = lower_bound_rows(
            vids,
            jnp.full((N,), vid_base[1]),
            jnp.full((N,), vid_base[1] + nverts[1]),
            v,
            n_iters=v_iters,
        )
        slot = jnp.where(found0, 0, 1).astype(jnp.int32)
        row = jnp.where(found0, r0 - vid_base[0], r1 - vid_base[1])
        row = jnp.clip(row, 0, None)
        return slot, row, found0 | found1

    def cond(state):
        _, _, _, _, resident, _, _, _, _, it = state
        return jnp.any(resident) & (it <= max_len)

    def body(state):
        prev_, cur_, hop_, alive_, resident, slot, row, steps_, trace_, it = state
        # counter-based keys: one stream per (walk id, hop)
        kw0, kw1 = rng.fold_in(*kwid, hop_)

        movable = resident  # alive & cur has a row in the pair
        # (slot, row) for cur_ is carried from the previous iteration's
        # locate(new_cur) — one remap search per hop, not two
        row_start = indptr[ptr_base[slot] + row]
        deg = indptr[ptr_base[slot] + row + 1] - row_start
        dead = movable & (deg <= 0)
        movable = movable & (deg > 0)
        deg_c = jnp.maximum(deg, 1)

        if order == 2:
            uslot, urow, _ = locate(prev_)
            u_start = indptr[ptr_base[uslot] + urow]
            ulo = ind_base[uslot] + u_start
            uhi = ulo + (indptr[ptr_base[uslot] + urow + 1] - u_start)

        # ---- proposal + rejection over k_max rounds -------------------------
        def propose(kk, carry):
            z_, accepted_ = carry
            u123 = rng.uniform3(*rng.fold_in(kw0, kw1, kk))
            kloc = jnp.minimum((u123[0] * deg_c).astype(jnp.int32), deg_c - 1)
            idx = ind_base[slot] + row_start + kloc
            if has_alias:
                take_alias = u123[1] >= alias_q[idx]
                kloc = jnp.where(take_alias, alias_j[idx], kloc)
                idx = ind_base[slot] + row_start + kloc
            zk = indices[idx]
            if order == 2:
                from repro.core.sampling import searchsorted_rows

                memb = searchsorted_rows(indices, ulo, uhi, zk, n_iters=n_iters)
                bias = jnp.where(zk == prev_, 1.0 / p, jnp.where(memb, 1.0, 1.0 / q))
                acc_p = bias / max_bias
                acc_p = jnp.where(hop_ == 0, 1.0, acc_p)  # first step: 1st-order
            else:
                acc_p = jnp.ones((N,), jnp.float32)
            last = kk == k_max - 1
            take = (~accepted_) & movable & ((u123[2] < acc_p) | last)
            z_ = jnp.where(take, zk, z_)
            return z_, accepted_ | take

        with jax.named_scope("advance.propose"):
            z, _ = jax.lax.fori_loop(0, k_max, propose, (cur_, ~movable))

        # ---- commit (the remap search of the new cur nests in its scope) -----
        with jax.named_scope("advance.hop"):
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
                cols = jnp.where(movable, jnp.clip(new_hop, 0, max_len), max_len + 1)
                trace_ = trace_.at[iota, cols].set(new_cur)
            steps_ = steps_ + movable.astype(jnp.int32).sum()
        return (
            new_prev,
            new_cur,
            new_hop,
            new_alive,
            new_resident,
            new_slot,
            new_row,
            steps_,
            trace_,
            it + 1,
        )

    slot0, row0, found0 = locate(cur)
    resident0 = alive & found0
    init = (
        prev,
        cur,
        hop,
        alive,
        resident0,
        slot0,
        row0,
        jnp.zeros((), jnp.int32),
        trace0,
        jnp.zeros((), jnp.int32),
    )
    prev_f, cur_f, hop_f, alive_f, _, _, _, steps, trace, _ = jax.lax.while_loop(cond, body, init)
    if record:
        trace = trace[:, : max_len + 1]
    return prev_f, cur_f, hop_f, alive_f, steps, trace


#: jitted entry point (host engines); the raw impl is reused inside shard_map
advance_pair = partial(
    jax.jit,
    static_argnames=("order", "k_max", "n_iters", "v_iters", "record", "has_alias", "max_len"),
)(pair_advance_impl)


def pow2_pad(n: int, lo: int = 256) -> int:
    """Next power of two >= n (>= lo) — static shapes for the jit cache."""
    m = lo
    while m < n:
        m <<= 1
    return m
