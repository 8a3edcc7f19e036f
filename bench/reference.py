"""The plain reference: what a node2vec walk over the cell's graph must be.

It reads only the CSR that the benchmark generated (never the block file, the
block store, the walk pool or the program's advance) and judges the walks the
timed path produced:

* every recorded hop is an edge of the graph (``invalid_hops``, exact);
* the second-order hops follow node2vec's law (``bias_z``).  A hop from
  ``cur`` with previous vertex ``prev`` lands on ``prev`` (return), on a
  common neighbour of ``prev`` and ``cur``, or elsewhere; node2vec weighs
  these ``1/p``, ``1`` and ``1/q``.  The engine draws by rejection with at
  most ``k_max`` uniform proposals and keeps the last proposal when all are
  rejected, so a category with ``n`` of the ``d`` neighbours and acceptance
  ``a`` has probability ``n/d * (a * (1 - r**(k-1)) / (1 - r) + r**(k-1))``
  with ``r`` the mean rejection.  Over a sample of hops drawn from the seed,
  ``bias_z`` is the largest |observed - expected| / sd of the three counts.

The control breaks the node2vec guarantee: :func:`first_order_next` draws the
same hops uniformly (p = q = 1), as a walker that dropped the second-order
bias would.  numpy only; nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


class ReferenceGraph:
    """Undirected simple graph in CSR form with vectorised edge lookups."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices, np.int64)
        self.num_vertices = self.indptr.size - 1
        self.degree = np.diff(self.indptr)
        rows = np.repeat(np.arange(self.num_vertices, dtype=np.int64), self.degree)
        # rows ascend and each row is sorted, so the keys are sorted
        self.keys = rows * self.num_vertices + self.indices

    def has_edge(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        ok = (u >= 0) & (u < self.num_vertices) & (v >= 0) & (v < self.num_vertices)
        key = np.where(ok, u * self.num_vertices + v, -1)
        pos = np.minimum(np.searchsorted(self.keys, key), self.keys.size - 1)
        return ok & (self.keys[pos] == key)

    def common_neighbours(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """|N(u) & N(v)| for each pair, scanning the shorter row."""
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        small = np.where(self.degree[u] <= self.degree[v], u, v)
        large = np.where(self.degree[u] <= self.degree[v], v, u)
        lens = self.degree[small]
        pair = np.repeat(np.arange(u.size), lens)
        offs = np.arange(lens.sum()) - np.repeat(np.cumsum(lens) - lens, lens)
        nbrs = self.indices[self.indptr[small][pair] + offs]
        hit = self.has_edge(large[pair], nbrs)
        return np.bincount(pair, weights=hit, minlength=u.size).astype(np.int64)


def node2vec_category_probs(
    degree: np.ndarray, common: np.ndarray, *, p: float, q: float, k_max: int
) -> np.ndarray:
    """``[n, 3]`` probabilities of (return, common neighbour, other)."""
    d = degree.astype(np.float64)
    m = max(1.0, 1.0 / p, 1.0 / q)
    acc = np.array([1.0 / p, 1.0, 1.0 / q]) / m
    n = np.stack([np.ones_like(d), common.astype(np.float64), d - 1.0 - common], 1)
    mean_acc = (n * acc).sum(1) / d
    r = 1.0 - mean_acc
    tail = r ** (k_max - 1)
    # sum_{j < k-1} r**j; r < 1 because every acceptance is above 0
    geo = (1.0 - tail) / np.maximum(1.0 - r, 1e-300)
    return n / d[:, None] * (acc[None, :] * geo[:, None] + tail[:, None])


def hop_categories(g: ReferenceGraph, prev: np.ndarray, nxt: np.ndarray) -> np.ndarray:
    """0 = returned to prev, 1 = common neighbour of prev, 2 = other."""
    cat = np.full(prev.size, 2, np.int64)
    cat[g.has_edge(prev, nxt)] = 1
    cat[nxt == prev] = 0
    return cat


def category_z(observed: np.ndarray, probs: np.ndarray) -> float:
    """Largest |O - E| / sd over the categories, sums over independent hops."""
    obs = np.bincount(observed, minlength=probs.shape[1])
    exp = probs.sum(0)
    var = (probs * (1.0 - probs)).sum(0)
    z = np.abs(obs - exp) / np.sqrt(np.maximum(var, 1e-12))
    return float(z.max())


def first_order_next(g: ReferenceGraph, cur: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The control: the next vertex drawn uniformly from N(cur) (p = q = 1)."""
    deg = g.degree[cur]
    k = np.minimum((rng.random(cur.size) * deg).astype(np.int64), deg - 1)
    return g.indices[g.indptr[cur] + k]


def bias_z(
    g: ReferenceGraph,
    prev: np.ndarray,
    cur: np.ndarray,
    nxt: np.ndarray,
    *,
    p: float,
    q: float,
    k_max: int,
) -> float:
    """node2vec's law against a set of second-order hops (valid edges only)."""
    if prev.size == 0:
        return 0.0
    probs = node2vec_category_probs(
        g.degree[cur], g.common_neighbours(prev, cur), p=p, q=q, k_max=k_max
    )
    return category_z(hop_categories(g, prev, nxt), probs)


def walk_hops(rows: np.ndarray, lo: np.ndarray, hi: np.ndarray):
    """Hops written into ``rows`` at columns ``[lo, hi)`` (per row), as
    ``(prev, cur, nxt, col)``; ``prev`` is -1 for the first hop of a walk."""
    n = np.maximum(hi - lo, 0)
    row = np.repeat(np.arange(rows.shape[0]), n)
    col = np.repeat(lo, n) + (np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n))
    nxt = rows[row, col].astype(np.int64)
    cur = rows[row, col - 1].astype(np.int64)
    prev = np.where(col >= 2, rows[row, np.maximum(col - 2, 0)], -1).astype(np.int64)
    return prev, cur, nxt, col


def audit_hops(
    g: ReferenceGraph,
    prev: np.ndarray,
    cur: np.ndarray,
    nxt: np.ndarray,
    *,
    walk: dict,
    k_max: int,
    sample: int,
    rng: np.random.Generator,
    control: bool = False,
) -> dict:
    """``invalid_hops`` over every hop, ``bias_z`` over a seeded sample of the
    second-order ones; with ``control`` the sampled hops are redrawn first-order."""
    valid = g.has_edge(cur, nxt)
    second = np.nonzero(valid & (prev >= 0) & g.has_edge(prev, cur))[0]
    pick = second if second.size <= sample else rng.choice(second, sample, replace=False)
    pick.sort()
    drawn = first_order_next(g, cur[pick], rng) if control else nxt[pick]
    z = bias_z(g, prev[pick], cur[pick], drawn, p=walk["p"], q=walk["q"], k_max=k_max)
    return {"invalid_hops": int((~valid).sum()), "bias_z": z, "hops": int(valid.size)}
