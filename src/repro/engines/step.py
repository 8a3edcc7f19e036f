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
from typing import Tuple

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
    "stage_widths",
]

#: vids padding value — sorts after every real vertex id
VID_PAD = jnp.iinfo(jnp.int32).max

#: narrowest loop width of the staged advance
STAGE_FLOOR = 256


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


def stage_widths(n: int) -> Tuple[int, ...]:
    """Loop widths of the staged advance for ``n`` lanes: ``n``, then
    ``max(256, n/4)`` and ``max(256, n/32)``, each kept only where it is
    narrower than the stage before (so ``n <= 256`` is one stage)."""
    widths = [n]
    for w in (max(STAGE_FLOOR, n // 4), max(STAGE_FLOOR, n // 32)):
        if w < widths[-1]:
            widths.append(w)
    return tuple(widths)


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
    terminates.  Returns ``(prev, cur, hop, alive, steps_taken, trace,
    lane_iters)`` where ``trace[n, h]`` is the vertex walk n reached at hop
    h during this call (-1 = no move) and ``lane_iters`` the lanes the loop
    ran, summed over its iterations.

    The hop loop runs in stages of :func:`stage_widths`: a stage leaves once
    its resident walks fit the next, narrower width, and the next stage
    carries only those walks.  A walk that is not resident never moves
    again within the call, and every draw is keyed by (walk id, hop, round),
    so the staging changes no output but ``lane_iters``.
    """
    N = prev.shape[0]
    max_bias = jnp.maximum(1.0, jnp.maximum(1.0 / p, 1.0 / q))
    # per-walk streams: fold the walk id in once, the hop/round per draw —
    # all through the shared hand-rolled threefry (repro.kernels.rng), the
    # same primitive the fused Pallas kernel lowers under Mosaic
    kwid = rng.fold_in(*rng.key_halves(key), wid)
    # one spare "dump" column (max_len+1) absorbs writes of frozen walks
    trace = jnp.full((N, max_len + 2) if record else (1, 1), -1, dtype=jnp.int32)

    @jax.named_scope("advance.locate")
    def locate(v):
        """Resolve global vertex -> (slot, compact row, found) via the remap."""
        W = v.shape[0]
        r0, found0 = lower_bound_rows(
            vids,
            jnp.full((W,), vid_base[0]),
            jnp.full((W,), vid_base[0] + nverts[0]),
            v,
            n_iters=v_iters,
        )
        r1, found1 = lower_bound_rows(
            vids,
            jnp.full((W,), vid_base[1]),
            jnp.full((W,), vid_base[1] + nverts[1]),
            v,
            n_iters=v_iters,
        )
        slot = jnp.where(found0, 0, 1).astype(jnp.int32)
        row = jnp.where(found0, r0 - vid_base[0], r1 - vid_base[1])
        row = jnp.clip(row, 0, None)
        return slot, row, found0 | found1

    # a stage's carry: the lanes (walk state, where the walk's cur sits, its
    # RNG stream, its row ``orig`` in the call's batch), then the scalars
    def body(state):
        lanes, steps_, trace_, it = state
        prev_, cur_, hop_, alive_, resident, slot, row, kwid0, kwid1, orig = lanes
        # counter-based keys: one stream per (walk id, hop)
        kw0, kw1 = rng.fold_in(kwid0, kwid1, hop_)

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
                acc_p = jnp.ones(zk.shape, jnp.float32)
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
                # fill lanes of a compacted stage carry orig = N: dropped
                cols = jnp.where(movable, jnp.clip(new_hop, 0, max_len), max_len + 1)
                trace_ = trace_.at[orig, cols].set(new_cur, mode="drop")
            steps_ = steps_ + movable.astype(jnp.int32).sum()
        lanes = (
            new_prev,
            new_cur,
            new_hop,
            new_alive,
            new_resident,
            new_slot,
            new_row,
            kwid0,
            kwid1,
            orig,
        )
        return lanes, steps_, trace_, it + 1

    @jax.named_scope("advance.compact")
    def compact(lanes, width):
        """The resident lanes of a stage, packed into ``width`` lanes.  The
        fill lanes are dead, so never resident, and write back nowhere."""
        resident = lanes[4]
        W = resident.shape[0]
        (sel,) = jnp.nonzero(resident, size=width, fill_value=W)
        kept = sel < W
        packed = [x[jnp.minimum(sel, W - 1)] for x in lanes]
        packed[3] = packed[3] & kept
        packed[4] = packed[4] & kept
        packed[9] = jnp.where(kept, packed[9], N)
        return tuple(packed)

    slot0, row0, found0 = locate(cur)
    lanes = (prev, cur, hop, alive, alive & found0, slot0, row0, *kwid, jnp.arange(N))
    walks = lanes[:4]  # prev, cur, hop, alive of every lane, by batch row
    steps = jnp.zeros((), jnp.int32)
    it = jnp.zeros((), jnp.int32)
    lane_iters = jnp.zeros((), jnp.int32)
    widths = stage_widths(N)
    for k, width in enumerate(widths):
        if k > 0:
            lanes = compact(lanes, width)
        w_next = widths[k + 1] if k + 1 < len(widths) else 0

        def cond(state, w_next=w_next):
            lanes_, _, _, it_ = state
            # more resident walks than the next stage holds (any, in the last)
            return (jnp.sum(lanes_[4], dtype=jnp.int32) > w_next) & (it_ <= max_len)

        it_in = it
        lanes, steps, trace, it = jax.lax.while_loop(cond, body, (lanes, steps, trace, it))
        lane_iters = lane_iters + (it - it_in) * width
        if k == 0:
            walks = lanes[:4]  # the first stage runs every lane in batch order
        else:
            walks = tuple(w.at[lanes[9]].set(x, mode="drop") for w, x in zip(walks, lanes[:4]))
    prev_f, cur_f, hop_f, alive_f = walks
    if record:
        trace = trace[:, : max_len + 1]
    return prev_f, cur_f, hop_f, alive_f, steps, trace, lane_iters


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
