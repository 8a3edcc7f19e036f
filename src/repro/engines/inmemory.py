"""In-memory oracle / corpus generator (whole-graph fast path).

Runs the same jitted view-pair kernel as the out-of-core engines with the
whole graph packed into a single full view.  Because every random draw is
keyed per ``(walk id, hop)`` off the task seed, the oracle's walks are
*bit-identical* to the walks any out-of-core engine samples for the same
task — the strongest possible correctness pin for the engines.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import BlockedGraph
from repro.core.stats import IOStats
from repro.core.transition import Node2vec, WalkTask

from .base import WalkResult
from .step import advance_pair, pow2_pad, remap_search_iters

__all__ = ["InMemoryWalker"]


class InMemoryWalker:
    """Whole-graph walker: one jit'd while_loop over steps.  Ground truth for
    engine tests and the corpus generator feeding the LM data pipeline."""

    def __init__(self, bg: BlockedGraph, task: WalkTask, *, k_max: int = 16):
        if not hasattr(bg, "graph"):
            # e.g. repro.io.DiskBlockedGraph: rebuild the host CSR explicitly
            raise TypeError(
                "InMemoryWalker needs the in-RAM BlockedGraph; for a disk "
                "backend, wrap bg.read_csr() in a BlockedGraph first"
            )
        self.bg = bg
        self.task = task
        is_plain = isinstance(task.model, Node2vec) and task.model.p == task.model.q == 1.0
        self.k_max = 1 if is_plain else k_max
        if task.model.order == 1:
            self.k_max = 1

    def run(self, *, record_walks: bool = True) -> WalkResult:
        bg, task = self.bg, self.task
        g = bg.graph
        stats = IOStats()
        src = task.initial_walks(g.num_vertices)
        n = src.shape[0]
        V = g.num_vertices
        # the whole graph as one full view; slot 1 aliases slot 0
        vids = np.arange(V, dtype=np.int32)
        nverts = np.array([V, V], np.int32)
        base0 = np.zeros(2, np.int32)
        indptr = g.indptr.astype(np.int32)
        indices = g.indices.astype(np.int32)
        has_alias = g.weights is not None
        if has_alias:
            from repro.core.sampling import build_alias_rows

            alias_j, alias_q = build_alias_rows(indptr, V, max(g.num_edges, 1), g.weights)
        else:
            alias_j = np.zeros(1, np.int32)
            alias_q = np.ones(1, np.float32)

        N = pow2_pad(n)
        pad = N - n
        pad32 = lambda x: jnp.asarray(np.concatenate([x.astype(np.int32), np.zeros(pad, np.int32)]))
        alive = jnp.asarray(np.concatenate([np.ones(n, bool), np.zeros(pad, bool)]))
        wid = pad32(np.arange(n, dtype=np.int64))
        v_iters = remap_search_iters(V)
        t0 = time.perf_counter()
        out = advance_pair(
            jnp.asarray(vids),
            jnp.asarray(nverts),
            jnp.asarray(base0),
            jnp.asarray(indptr),
            jnp.asarray(base0),
            jnp.asarray(indices),
            jnp.asarray(base0),
            jnp.asarray(alias_j),
            jnp.asarray(alias_q),
            wid,
            pad32(src),
            pad32(src),
            pad32(np.zeros(n)),
            alive,
            jax.random.PRNGKey(task.seed),
            jnp.int32(task.length),
            jnp.float32(task.decay),
            jnp.float32(getattr(task.model, "p", 1.0)),
            jnp.float32(getattr(task.model, "q", 1.0)),
            order=task.model.order,
            k_max=self.k_max,
            n_iters=int(np.ceil(np.log2(max(g.num_edges, 2)))) + 2,
            v_iters=v_iters,
            record=record_walks,
            has_alias=has_alias,
            max_len=int(task.length),
        )
        prev_f, cur_f, hop_f, alive_f, steps, trace, _ = jax.tree.map(
            np.asarray, jax.block_until_ready(out)
        )
        stats.exec_time = time.perf_counter() - t0
        stats.steps_sampled = int(steps)
        counts = np.bincount(cur_f[:n], minlength=g.num_vertices).astype(np.int64)
        corpus = None
        if record_walks:
            corpus = np.full((n, task.length + 1), -1, np.int32)
            corpus[:, 0] = src
            t = trace[:n]
            for h in range(1, task.length + 1):
                m = t[:, h] >= 0
                corpus[m, h] = t[m, h]
        return WalkResult(n, int(steps), counts, corpus, stats)
