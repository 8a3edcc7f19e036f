"""The plain reference: the benchmark's graph, node2vec's law as the audit
states it, and the control that must fail it."""

from __future__ import annotations

import numpy as np
import pytest

from benchtest_util import tiny_run

import reference
from graph import build_graph


@pytest.fixture(scope="module")
def small_graph():
    spec = {"generator": "graph500_kronecker", "seed": 5, "scale": 9, "edge_factor": 16,
            "a": 0.57, "b": 0.19, "c": 0.19}
    indptr, indices, starts = build_graph(spec, seed=1, num_blocks=4)
    g = reference.ReferenceGraph(indptr, indices)
    g.starts = starts
    return g


def test_seeds_relabel_inside_fixed_blocks():
    """Another seed is another graph with the same blocks and degrees per block."""
    spec = {"generator": "graph500_kronecker", "seed": 5, "scale": 9, "edge_factor": 16,
            "a": 0.57, "b": 0.19, "c": 0.19}
    (p1, i1, s1), (p2, i2, s2) = (build_graph(spec, seed=k, num_blocks=4) for k in (1, 2))
    assert (s1 == s2).all() and (p1[s1] == p2[s2]).all()
    assert not np.array_equal(i1, i2)
    for lo, hi in zip(s1[:-1], s1[1:]):
        d1, d2 = np.diff(p1)[lo:hi], np.diff(p2)[lo:hi]
        assert sorted(d1) == sorted(d2)


def test_graph_is_undirected_simple_and_sorted(small_graph):
    g = small_graph
    rows = np.repeat(np.arange(g.num_vertices), g.degree)
    assert (rows != g.indices).all()  # no self loops
    assert (np.diff(g.keys) > 0).all()  # sorted rows, no duplicates
    assert g.has_edge(g.indices, rows).all()  # symmetric
    # Graph500 permutes the labels: the lowest eighth of the ids holds about
    # an eighth of the edges, not the 44% that unpermuted Kronecker ids hold
    assert g.indptr[g.num_vertices // 8] < 0.25 * g.indptr[-1]


def _node2vec_next(g, prev, cur, rng, *, p, q, k_max):
    """An independent sampler of the engine's rule: up to k_max uniform
    proposals accepted with bias / max bias, the last one taken regardless."""
    out = np.empty_like(cur)
    m = max(1.0, 1 / p, 1 / q)
    for n, (u, v) in enumerate(zip(prev, cur)):
        nbrs = g.indices[g.indptr[v] : g.indptr[v + 1]]
        for k in range(k_max):
            z = nbrs[rng.integers(nbrs.size)]
            bias = 1 / p if z == u else (1.0 if g.has_edge(np.array([u]), np.array([z]))[0] else 1 / q)
            if k == k_max - 1 or rng.random() < bias / m:
                out[n] = z
                break
    return out


def _contexts(g, rng, n):
    cur = rng.integers(0, g.num_vertices, 4 * n)
    cur = cur[g.degree[cur] > 0][:n]
    k = (rng.random(cur.size) * g.degree[cur]).astype(np.int64)
    prev = g.indices[g.indptr[cur] + k]
    return prev, cur


def test_law_holds_for_an_independent_sampler(small_graph):
    rng = np.random.default_rng(11)
    prev, cur = _contexts(small_graph, rng, 4000)
    for k_max in (16, 2):
        nxt = _node2vec_next(small_graph, prev, cur, rng, p=4.0, q=0.25, k_max=k_max)
        z = reference.bias_z(small_graph, prev, cur, nxt, p=4.0, q=0.25, k_max=k_max)
        assert z < 4.5


def test_first_order_control_fails_the_law(small_graph):
    rng = np.random.default_rng(12)
    prev, cur = _contexts(small_graph, rng, 4000)
    nxt = reference.first_order_next(small_graph, cur, rng)
    assert reference.bias_z(small_graph, prev, cur, nxt, p=4.0, q=0.25, k_max=16) > 20


@pytest.mark.parametrize("workload", ["rwnv.kron20"])
def test_control_in_the_programs_place_is_not_correct(workload):
    """The window's own hops redrawn by the control fail ``bias_z``; the
    program's pass."""
    result, _ = tiny_run(workload, seconds=1.0, control=True)
    assert result["correct"] is True, result["checks"]
    ctrl = result["control_checks"]["bias_z"]
    assert ctrl["value"] > ctrl["limit"]


def test_graph_made_in_a_child_is_the_graph_made_here():
    """The run's child process hands back, through its pipe, the arrays that
    the same spec and seed give in this process."""
    from graph import build_graph_in_child

    spec = {"generator": "graph500_kronecker", "seed": 5, "scale": 9, "edge_factor": 16,
            "a": 0.57, "b": 0.19, "c": 0.19}
    here = build_graph(spec, seed=3, num_blocks=4)
    there = build_graph_in_child(spec, 3, 4, chips=0)
    assert len(there) == 3
    for a, b in zip(here, there):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_graph_child_without_a_chip_exits_3():
    import graph

    spec = {"generator": "graph500_kronecker", "seed": 5, "scale": 9, "edge_factor": 16,
            "a": 0.57, "b": 0.19, "c": 0.19}
    with pytest.raises(graph.ChildFailed) as e:
        graph.build_graph_in_child(spec, 3, 4, chips=1)
    assert e.value.code == 3
