"""``batch``: the corpus a window recorded, against the plain reference
(:mod:`reference`), each number beside its limit.

* ``source_mismatch``  rows whose first vertex is not the walk's source;
* ``unrecorded_steps`` |steps the program counted in the window - hops
  written into the corpus in the window|;
* ``invalid_hops``     hops of the window that are not edges of the graph;
* ``bias_z``           node2vec's law over a seeded sample of those hops;
* ``short_walks``      walks that the window retired before their length,
  at a vertex that has neighbours.

Every limit comes from the configuration's ``audit.limits``, and the
sample size of ``bias_z`` from ``audit.bias_sample``; a number passes when
it is at most its limit.  A mode whose result has this kind returns the
keys read below: ``corpus``, ``sources``, ``filled_open``, ``filled_close``,
``ended``, ``walk``, ``k_max`` and the ``window``.
"""

from __future__ import annotations

import numpy as np

import reference


def _check(name: str, value, limits: dict) -> dict:
    return {"name": name, "value": value, "limit": limits[name]}


def check(out: dict, graph: reference.ReferenceGraph, audit: dict, rng, *, control=False):
    if out.get("corpus") is None:
        raise ValueError("the reference audits the recorded walks: record_walks must be on")
    limits = audit["limits"]
    corpus = out["corpus"]
    prev, cur, nxt, _ = reference.walk_hops(corpus, out["filled_open"], out["filled_close"])
    hops = reference.audit_hops(
        graph,
        prev,
        cur,
        nxt,
        walk=out["walk"],
        k_max=out["k_max"],
        sample=audit["bias_sample"],
        rng=rng,
        control=control,
    )
    steps = out["window"].counters["steps_sampled"]
    ended = out["ended"]
    rows = corpus[ended]
    done = (rows >= 0).sum(1) - 1
    last = rows[np.arange(ended.size), np.maximum(done, 0)]
    short = (done < out["walk"]["length"]) & (graph.degree[last] > 0)
    return [
        _check("source_mismatch", int((corpus[:, 0] != out["sources"]).sum()), limits),
        _check("unrecorded_steps", int(abs(steps - hops["hops"])), limits),
        _check("invalid_hops", hops["invalid_hops"], limits),
        _check("bias_z", hops["bias_z"], limits),
        _check("short_walks", int(short.sum()), limits),
    ]
