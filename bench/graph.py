"""Graph data of a cell, made from the seed on the device in bulk.

The Graph500 Kronecker generator (graph500.org specification: initiator
probabilities A, B, C and D = 1 - A - B - C, ``edge_factor`` edges per vertex):
every edge picks one quadrant per bit of ``scale``, and the vertex labels are
then permuted at random, as the specification does, so the hubs are spread
over the id range.

The edge list is symmetrised, self loops and duplicates are dropped, and the
rows are sorted: the undirected simple graph that GraSorw's experiments use.
The result is a plain host CSR (``indptr`` int64, ``indices`` int32) with its
block starts, which the benchmark hands to the program and the reference
reads on its own.

The generator's sort holds many times the graph on the device, far more than
the system under test ever does.  A run therefore makes the graph in a child
process of its own (:func:`build_graph_in_child`, this file run as a script),
which hands the arrays back through a pipe and exits before the run's own
process touches the chip: the run's ``memory_peak_bytes`` is then the
system's.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("scale", "edge_factor"))
def _kronecker_edges(key, a, b, c, *, scale: int, edge_factor: int):
    """Sorted, symmetrised, simple edge list; dropped entries have ``keep`` false."""
    m = edge_factor << scale
    n = 1 << scale
    a_norm = a / (a + b)
    c_norm = c / (1.0 - a - b)
    k_edges, k_perm = jax.random.split(key)

    def bit(i, carry):
        src, dst = carry
        k1, k2 = jax.random.split(jax.random.fold_in(k_edges, i))
        src_bit = jax.random.uniform(k1, (m,)) >= a + b
        thr = jnp.where(src_bit, c_norm, a_norm)
        dst_bit = jax.random.uniform(k2, (m,)) >= thr
        src = src | (src_bit.astype(jnp.int32) << i)
        dst = dst | (dst_bit.astype(jnp.int32) << i)
        return src, dst

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    s = jnp.concatenate([src, dst])
    d = jnp.concatenate([dst, src])
    s = jnp.where(s == d, n, s)  # self loops sort last and are dropped
    return _sorted_csr(s, d, n)


def _sorted_csr(s, d, n: int):
    s, d = jax.lax.sort((s, d), num_keys=2)
    first = jnp.arange(s.shape[0]) == 0
    new = first | (s != jnp.roll(s, 1)) | (d != jnp.roll(d, 1))
    keep = new & (s < n)
    counts = jnp.zeros((n + 1,), jnp.int32).at[s].add(keep.astype(jnp.int32))[:n]
    return s, d, keep, counts


@jax.jit
def _relabel(s, d, keep, perm):
    n = perm.shape[0]
    s2 = jnp.where(keep, perm[jnp.minimum(s, n - 1)], n)
    d2 = jnp.where(keep, perm[d], 0)
    return _sorted_csr(s2, d2, n)


def edge_balanced_starts(degrees: np.ndarray, num_blocks: int) -> np.ndarray:
    """Block starts of equal edge count: each boundary is the first vertex
    whose cumulative degree reaches ``b * E / num_blocks`` (the program's
    ``partition_into_n_blocks`` rule)."""
    n = degrees.size
    cum = np.cumsum(degrees.astype(np.int64))
    target = max(int(cum[-1]) // num_blocks, 1)
    starts = [0]
    for b in range(1, num_blocks):
        v = int(np.searchsorted(cum, b * target, side="left")) + 1
        v = min(max(v, starts[-1] + 1), n - (num_blocks - b))
        starts.append(v)
    starts.append(n)
    return np.asarray(starts, np.int64)


def build_graph(spec: dict, seed: int, num_blocks: int):
    """The cell's graph: ``(indptr, indices, block_starts)``.

    The Kronecker draw, its Graph500 permutation included, comes from the
    configuration's fixed ``spec["seed"]``, and the run's ``seed`` relabels
    the vertices at random *inside* each edge-balanced block.  Every seed so
    gets a different graph with the same block sizes and degrees per block:
    the program compiles the same shapes for every seed, and the seed
    changes the walks and not the amount of work.
    """
    if spec["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown graph generator {spec['generator']!r}")
    scale = spec["scale"]
    n = 1 << scale
    edges = _kronecker_edges(
        jax.random.PRNGKey(spec["seed"]),
        jnp.float32(spec["a"]),
        jnp.float32(spec["b"]),
        jnp.float32(spec["c"]),
        scale=scale,
        edge_factor=spec["edge_factor"],
    )
    starts = edge_balanced_starts(np.asarray(edges[3]), num_blocks)
    rng = np.random.default_rng(seed)
    perm = np.concatenate(
        [lo + rng.permutation(hi - lo) for lo, hi in zip(starts[:-1], starts[1:])]
    ).astype(np.int32)
    _, d, keep, counts = _relabel(*edges[:3], jnp.asarray(perm))
    del edges
    keep = np.asarray(keep)
    indices = np.asarray(d)[keep].astype(np.int32)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.asarray(counts, np.int64), out=indptr[1:])
    return indptr, indices, starts


# -- the child process ----------------------------------------------------------
class ChildFailed(RuntimeError):
    """The graph's child process exited with ``code`` and no graph."""

    def __init__(self, code: int):
        super().__init__(f"the graph's child process exited with code {code}")
        self.code = code


def _write_arrays(f, arrays) -> None:
    head = [{"dtype": a.dtype.str, "shape": list(a.shape)} for a in arrays]
    f.write((json.dumps(head) + "\n").encode())
    for a in arrays:
        f.write(np.ascontiguousarray(a).data.cast("B"))


def _read_arrays(f) -> list:
    head = f.readline()
    if not head:
        return []
    out = []
    for h in json.loads(head):
        a = np.empty(h["shape"], np.dtype(h["dtype"]))
        view = memoryview(a).cast("B")
        got = 0
        while got < view.nbytes:
            k = f.readinto(view[got:])
            if not k:
                raise EOFError("the graph's child process ended mid-array")
            got += k
        out.append(a)
    return out


def build_graph_in_child(spec: dict, seed: int, num_blocks: int, *, chips: int):
    """:func:`build_graph` in a child process that first checks for ``chips``
    TPU chips (none when ``chips`` is 0) and exits 3 without them.  Call it
    before this process touches JAX's backend: the child needs the chip."""
    r, w = os.pipe()
    cmd = [sys.executable, str(Path(__file__).resolve()), "--fd", str(w), "--seed", str(seed),
           "--blocks", str(num_blocks), "--chips", str(chips), "--spec", json.dumps(spec)]
    proc = subprocess.Popen(cmd, pass_fds=(w,))
    os.close(w)
    try:
        with os.fdopen(r, "rb") as f:
            arrays = _read_arrays(f)
    finally:
        code = proc.wait()
    if code != 0 or len(arrays) != 3:
        raise ChildFailed(code or 1)
    return tuple(arrays)


def _child(argv=None) -> int:
    import argparse

    import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--fd", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--blocks", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--spec", required=True)
    args = ap.parse_args(argv)
    harness.ensure_paths()
    try:
        if args.chips:
            harness.require_chip(args.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    arrays = build_graph(json.loads(args.spec), args.seed, args.blocks)
    with os.fdopen(args.fd, "wb") as f:
        _write_arrays(f, arrays)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(_child())
