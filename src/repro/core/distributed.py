"""Distributed GraSorw — the bi-block engine at pod scale via shard_map.

Mapping (DESIGN.md §2/§5): at pod scale the "disk" is *remote HBM* and a
"block I/O" is a sequential shard transfer over ICI.  Each `model`-axis rank
owns one graph block; walks are sharded over (`data` x `model`).  The
triangular bi-block schedule becomes a **half-ring** schedule:

    for t in 1 .. floor(N_B / 2):
        every rank r holds the pair (block r, block (r + t) mod N_B)
        — one collective_permute per round moves the partner shard —
        and advances every routed walk whose block pair has ring distance t.

Every unordered block pair {a, b} is resident at exactly one rank per sweep
(rank a if (b-a) mod N_B <= N_B/2 else rank b; ties toward min(a, b)) —
precisely the paper's "visit each pair once per sweep, skewed to one side":
Eq. 3's ~50 % block-I/O saving, expressed as ring rounds instead of reads.
Walks are routed to the owning rank with an `all_to_all` (the bucket I/O of
§4.3, now one fused sequential transfer per round) under a static
per-destination capacity; overflow walks wait a round (correctness is
unaffected — a walk only moves when its pair is resident).

Between sweeps, walk state crosses the host boundary through the **shared
sharded walk pool** (:class:`repro.io.ShardedWalkPool`) instead of private
driver arrays: the live frontier is persisted with the same block
association the single-host engines use (skewed ``min(B(u), B(v))``, or
``B(cur)`` for first order) and drained back — scattered to its global
walk-id slot — before the next sweep.  The pool is the same storage tier
the out-of-core engines spill through, so a disk-backed pool moves real
16-byte records and the walk-I/O charges land in the engine's
:class:`~repro.core.stats.IOStats`.  Because the kernel's RNG is
counter-based per (walk id, hop), the roundtrip changes nothing about the
sampled trajectories.

The per-walk step math is `pair_advance_impl` — the same function the
single-host engines jit, drawing through the hand-rolled
:mod:`repro.kernels.rng` threefry (shared with the fused Pallas kernel),
which lowers cleanly inside `shard_map`.  One sampler, one RNG, three
deployment tiers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .engine import pair_advance_impl
from repro.engines.step import VID_PAD, remap_search_iters
from repro.io import ShardedWalkPool
from .buckets import push_by_block_assignment
from .graph import BlockedGraph
from .stats import IOStats
from .transition import Node2vec, WalkTask
from .walk import WalkBatch

__all__ = ["DistributedWalkEngine", "ring_owner_and_round"]


def ring_owner_and_round(a, b, nb: int):
    """Owner rank and ring round for block pair (a, b). Pure / vectorised."""
    d_ab = (b - a) % nb
    d_ba = (a - b) % nb
    tie = d_ab == d_ba  # nb even, distance nb/2
    a_owns = (d_ab < d_ba) | (tie & (a <= b))
    owner = jnp.where(a_owns, a, b)
    rnd = jnp.where(a_owns, d_ab, d_ba)
    rnd = jnp.where(a == b, 0, rnd)
    owner = jnp.where(a == b, a, owner)
    return owner.astype(jnp.int32), rnd.astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockShards:
    start: jax.Array  # [NB]  P('model')
    nverts: jax.Array  # [NB]
    indptr: jax.Array  # [NB, MV+1]  P('model', None)
    indices: jax.Array  # [NB, ME]
    alias_j: jax.Array
    alias_q: jax.Array


class DistributedWalkEngine:
    """Walks sharded over (data x model); blocks sharded over 'model'.

    Requires ``bg.num_blocks == mesh.shape[block_axis]`` (one block shard per
    model rank — the natural pod-scale deployment).  Walk state persists
    between sweeps through a shared :class:`repro.io.ShardedWalkPool`
    (``pool``/``pool_shards``/``pool_flush_walks``/``pool_dir``; pass a pool
    instance to share one across engines — the engine then never closes it).
    """

    def __init__(
        self,
        bg: BlockedGraph,
        task: WalkTask,
        mesh: Mesh,
        *,
        data_axes: Tuple[str, ...] = ("data",),
        block_axis: str = "model",
        capacity_factor: float = 2.0,
        k_max: int = 16,
        pool: Union[str, ShardedWalkPool] = "memory",
        pool_shards: Optional[int] = None,
        pool_flush_walks: Optional[int] = 1 << 18,
        pool_dir: Optional[str] = None,
        stats: Optional[IOStats] = None,
    ):
        nb = mesh.shape[block_axis]
        if bg.num_blocks != nb:
            raise ValueError(
                f"num_blocks ({bg.num_blocks}) must equal mesh[{block_axis!r}] ({nb})"
            )
        self.bg = bg
        self.task = task
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.block_axis = block_axis
        self.walk_axes = (*self.data_axes, block_axis)
        self.nb = nb
        self.capacity_factor = capacity_factor
        self.order = task.model.order
        if isinstance(pool, str):
            self.stats = stats if stats is not None else IOStats()
            # one writer shard per model rank by default (shard_of_block
            # stripes, so num_shards == num_blocks is the identity) — the
            # natural deployment where each rank drains its own block pools
            self.pool = ShardedWalkPool(
                pool,
                num_shards=nb if pool_shards is None else pool_shards,
                num_blocks=nb,
                stats=self.stats,
                block_starts=bg.block_starts,
                flush_walks=pool_flush_walks,
                directory=pool_dir,
            )
            self._owns_pool = True
        else:
            self.pool = pool
            self._owns_pool = False
            # a shared pool charges the stats it was built with — report
            # those, not a fresh bundle that never sees its walk I/O
            if stats is None:
                stats = getattr(pool, "stats", None)
            self.stats = stats if stats is not None else IOStats()
        first_order = task.model.order == 1
        trivial_nv = isinstance(task.model, Node2vec) and task.model.p == task.model.q == 1.0
        self.k_max = 1 if first_order or trivial_nv else k_max
        self.n_iters = int(np.ceil(np.log2(max(bg.max_block_edges, 2)))) + 2
        #: the graph's blocks on device, block b on `block_axis` rank b
        self.block_shards = self._stack_blocks()

    # -- block shards ------------------------------------------------------
    def _stack_blocks(self) -> BlockShards:
        bg = self.bg
        nb, mv, me = bg.num_blocks, bg.max_block_verts, bg.max_block_edges
        start = np.zeros(nb, np.int32)
        nverts = np.zeros(nb, np.int32)
        indptr = np.zeros((nb, mv + 1), np.int32)
        indices = np.full((nb, me), -1, np.int32)
        alias_j = np.zeros((nb, me), np.int32)
        alias_q = np.ones((nb, me), np.float32)
        for b in range(nb):
            blk = bg.materialize_block(b)
            start[b], nverts[b] = blk.start, blk.nverts
            indptr[b] = blk.indptr
            indices[b] = blk.indices
            if blk.alias_j is not None:
                alias_j[b], alias_q[b] = blk.alias_j, blk.alias_q
        sh1 = NamedSharding(self.mesh, P(self.block_axis))
        sh2 = NamedSharding(self.mesh, P(self.block_axis, None))
        return BlockShards(
            jax.device_put(start, sh1),
            jax.device_put(nverts, sh1),
            jax.device_put(indptr, sh2),
            jax.device_put(indices, sh2),
            jax.device_put(alias_j, sh2),
            jax.device_put(alias_q, sh2),
        )

    # -- the sharded sweep ----------------------------------------------------
    def _make_sweep(self, capacity: int):
        task, nb = self.task, self.nb
        k_max, n_iters = self.k_max, self.n_iters
        has_alias = self.bg.has_weights
        length = int(task.length)
        baxis = self.block_axis
        block_starts = jnp.asarray(self.bg.block_starts.astype(np.int32))
        OOB = nb * capacity  # out-of-bounds scatter target (mode="drop")

        def blk_of(v):
            return jnp.clip(
                jnp.searchsorted(block_starts, v, side="right") - 1, 0, nb - 1
            ).astype(jnp.int32)

        mv = self.bg.max_block_verts
        v_iters = remap_search_iters(mv)

        def sweep(blocks: BlockShards, prev, cur, hop, alive, key):
            # walk ids are global: linearise the shard rank over the walk
            # axes (matching P(walk_axes) layout) — the counter-based RNG
            # streams are then identical to the single-host engines'
            r = jnp.zeros((), jnp.int32)
            for ax in self.walk_axes:
                r = r * self.mesh.shape[ax] + jax.lax.axis_index(ax)
            own = jax.tree.map(lambda x: x[0], blocks)
            W = prev.shape[0]
            wid0 = r * W + jnp.arange(W, dtype=jnp.int32)

            def make_vids(start, nv):
                k = jnp.arange(mv, dtype=jnp.int32)
                return jnp.where(k < nv, start + k, VID_PAD)

            def round_body(t, state):
                prev, cur, hop, alive, partner, key = state
                # rotate partner shard one ring hop (sequential "block I/O")
                perm = [(i, (i - 1) % nb) for i in range(nb)]
                partner = jax.tree.map(lambda x: jax.lax.ppermute(x, baxis, perm), partner)
                # --- route walks to this round's owner ----------------------
                owner, rnd = ring_owner_and_round(blk_of(prev), blk_of(cur), nb)
                is_init = hop == 0
                owner = jnp.where(is_init, blk_of(cur), owner)
                rnd = jnp.where(is_init, t, rnd)
                want = alive & (rnd == t)
                dest = jnp.where(want, owner, nb)
                one_hot = jax.nn.one_hot(dest, nb + 1, dtype=jnp.int32)
                slot = jnp.cumsum(one_hot, axis=0)[jnp.arange(W), dest] - 1
                routed = want & (slot < capacity)
                flat = jnp.where(routed, dest * capacity + slot, OOB)
                payload = jnp.stack([prev, cur, hop, alive.astype(jnp.int32), wid0], -1)
                send = jnp.full((OOB, 5), -1, jnp.int32)
                send = send.at[flat].set(payload, mode="drop")
                recv = jax.lax.all_to_all(
                    send.reshape(nb, capacity, 5),
                    baxis,
                    split_axis=0,
                    concat_axis=0,
                ).reshape(OOB, 5)
                rmask = recv[:, 0] >= 0
                # --- advance on the resident view pair ----------------------
                own_vids = make_vids(own.start, own.nverts)
                partner_vids = make_vids(partner.start, partner.nverts)
                nprev, ncur, nhop, nalive, _, _, _ = pair_advance_impl(
                    jnp.concatenate([own_vids, partner_vids]),
                    jnp.stack([own.nverts, partner.nverts]),
                    jnp.array([0, mv], jnp.int32),
                    jnp.concatenate([own.indptr, partner.indptr]),
                    jnp.array([0, mv + 1], jnp.int32),
                    jnp.concatenate([own.indices, partner.indices]),
                    jnp.array([0, own.indices.shape[0]], jnp.int32),
                    jnp.concatenate([own.alias_j, partner.alias_j]),
                    jnp.concatenate([own.alias_q, partner.alias_q]),
                    jnp.where(rmask, recv[:, 4], 0),
                    recv[:, 0],
                    recv[:, 1],
                    recv[:, 2],
                    (recv[:, 3] > 0) & rmask,
                    key,
                    jnp.int32(length),
                    jnp.float32(task.decay),
                    jnp.float32(getattr(task.model, "p", 1.0)),
                    jnp.float32(getattr(task.model, "q", 1.0)),
                    order=task.model.order,
                    k_max=k_max,
                    n_iters=n_iters,
                    v_iters=v_iters,
                    record=False,
                    has_alias=has_alias,
                    max_len=length,
                )
                # --- send results back to the origin shard ------------------
                back = jnp.stack([nprev, ncur, nhop, nalive.astype(jnp.int32)], -1)
                back = jnp.where(rmask[:, None], back, -1)
                back = jax.lax.all_to_all(
                    back.reshape(nb, capacity, 4),
                    baxis,
                    split_axis=0,
                    concat_axis=0,
                ).reshape(OOB, 4)
                # invert the routing: flat slot -> local walk index
                home = jnp.full(OOB, -1, jnp.int32)
                home = home.at[flat].set(jnp.arange(W, dtype=jnp.int32), mode="drop")
                valid = (back[:, 0] >= 0) & (home >= 0)
                # invalid rows scatter out of bounds and are dropped — never
                # write a stale duplicate index (scatter order is undefined)
                tgt = jnp.where(valid, home, W)
                prev = prev.at[tgt].set(back[:, 0], mode="drop")
                cur = cur.at[tgt].set(back[:, 1], mode="drop")
                hop = hop.at[tgt].set(back[:, 2], mode="drop")
                alive = alive.at[tgt].set(back[:, 3] > 0, mode="drop")
                return prev, cur, hop, alive, partner, key

            rounds = max(nb // 2, 1)
            prev, cur, hop, alive, _, _ = jax.lax.fori_loop(
                1, rounds + 1, round_body, (prev, cur, hop, alive, own, key)
            )
            return prev, cur, hop, alive

        return sweep

    # -- walk persistence through the shared pool -----------------------------
    def _persist_frontier(self, src0, prev, cur, hop, alive) -> None:
        """Push the live frontier into the shared pool through the same
        persist helper the single-host engines use (one association rule,
        every tier); walk ids (== global array slots) ride along so the
        drain can scatter each walk back to its slot."""
        live = np.nonzero(alive)[0]
        if live.size == 0:
            return
        batch = WalkBatch(src0[live], prev[live], cur[live], hop[live])
        push_by_block_assignment(
            self.pool, self.bg.block_starts, self.order, batch, live.astype(np.int64)
        )

    def _drain_frontier(self, n_slots: int):
        """Drain every block pool and rebuild the dense sweep arrays by
        scattering each walk to its global walk-id slot, so the
        counter-based RNG streams are untouched by the pool roundtrip.
        All drains are enqueued first (in block order — the program-order
        subsequence per shard, hence deterministic charges) so the shard
        writers drain their disjoint blocks concurrently."""
        prev = np.zeros(n_slots, np.int32)
        cur = np.zeros(n_slots, np.int32)
        hop = np.zeros(n_slots, np.int32)
        alive = np.zeros(n_slots, bool)
        pending = [b for b in range(self.nb) if self.pool.counts[b] > 0]
        for fut in [self.pool.drain_async(b) for b in pending]:
            (batch, wid), _n_walks, _n_spilled = fut.result()
            prev[wid] = batch.prev
            cur[wid] = batch.cur
            hop[wid] = batch.hop
            alive[wid] = True
        return prev, cur, hop, alive

    # -- driver -------------------------------------------------------------
    def run(self, max_sweeps: Optional[int] = None) -> dict:
        task, bg = self.task, self.bg
        src = task.initial_walks(bg.num_vertices).astype(np.int32)
        n = src.shape[0]
        wshards = int(np.prod([self.mesh.shape[a] for a in self.walk_axes]))
        N = int(np.ceil(n / wshards) * wshards)
        pad = N - n
        src0 = np.concatenate([src, np.zeros(pad, np.int32)])
        capacity = max(int(np.ceil((N / wshards) / self.nb * self.capacity_factor)), 8)

        wspec = P(self.walk_axes)
        bspec = BlockShards(
            P(self.block_axis),
            P(self.block_axis),
            P(self.block_axis, None),
            P(self.block_axis, None),
            P(self.block_axis, None),
            P(self.block_axis, None),
        )
        sweep_fn = jax.jit(
            jax.shard_map(
                self._make_sweep(capacity),
                mesh=self.mesh,
                in_specs=(bspec, wspec, wspec, wspec, wspec, P()),
                out_specs=(wspec, wspec, wspec, wspec),
                check_vma=False,
            )
        )
        wsh = NamedSharding(self.mesh, wspec)
        # counter-based RNG: the base key is fixed; draws are keyed per
        # (walk id, hop) inside the kernel, so walks are bit-identical to
        # the single-host engines' for the same task seed
        key = jax.random.PRNGKey(task.seed)

        # the live frontier crosses sweeps through the shared pool; the
        # result arrays accumulate every walk's final state (a retired
        # walk's slot is last written the sweep it died in)
        host_prev = src0.copy()
        host_cur = src0.copy()
        host_hop = np.zeros(N, np.int32)
        host_alive = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
        res_prev = host_prev.copy()
        res_cur = host_cur.copy()
        res_hop = host_hop.copy()
        res_alive = host_alive.copy()

        sweeps = 0
        limit = max_sweeps if max_sweeps is not None else task.length + 8
        try:
            while sweeps < limit and host_alive.any():
                prev = jax.device_put(jnp.asarray(host_prev), wsh)
                cur = jax.device_put(jnp.asarray(host_cur), wsh)
                hop = jax.device_put(jnp.asarray(host_hop), wsh)
                alive = jax.device_put(jnp.asarray(host_alive), wsh)
                prev, cur, hop, alive = sweep_fn(self.block_shards, prev, cur, hop, alive, key)
                sweeps += 1
                live_in = host_alive
                host_prev = np.asarray(prev).astype(np.int32)
                host_cur = np.asarray(cur).astype(np.int32)
                host_hop = np.asarray(hop).astype(np.int32)
                host_alive = np.asarray(alive).astype(bool)
                # only walks alive going into the sweep were advanced there
                res_prev[live_in] = host_prev[live_in]
                res_cur[live_in] = host_cur[live_in]
                res_hop[live_in] = host_hop[live_in]
                res_alive[live_in] = host_alive[live_in]
                if not host_alive.any():
                    break
                self._persist_frontier(src0, host_prev, host_cur, host_hop, host_alive)
                host_prev, host_cur, host_hop, host_alive = self._drain_frontier(N)
        finally:
            if self._owns_pool:
                self.pool.close()
        return {
            "prev": res_prev[:n],
            "cur": res_cur[:n],
            "hop": res_hop[:n],
            "alive": res_alive[:n],
            "sweeps": sweeps,
            "stats": self.stats,
        }
