"""Bring-up smoke run of GraSorw-JAX on a TPU: the main path, once, at a real size.

    python3 chip_smoke.py             # phases (a)-(d) on one chip
    python3 chip_smoke.py --chips 4   # phase (e) only: the distributed sweep

Phases, each through the constructors the launchers use:

(a) bitwise   Graph500 Kronecker graph at scale 14 (A=0.57, B=0.19,
              C=0.19, edge factor 16) in 8 edge-balanced blocks of a packed
              block file; node2vec (p=4, q=0.25), one walk per vertex,
              length 20.  ``BiBlockEngine`` with ``loading="auto"`` and with
              ``loading="full"`` (disk pool, async pipeline) must give the
              in-memory oracle's endpoint counts bit for bit.
(b) batch     the same generator at scale 20 (1,048,576 vertices), node2vec
              walks of length 80 through ``BiBlockEngine`` with the disk
              pool and disk graph backend; a 4-block cache holds half the
              file.  Walks start from every 64th vertex (16,384 walks), so
              the phase takes minutes; the graph is not cut.  Every walk
              must end.
(c) serve     ``WalkQueryServer`` on the scale-20 block file answers 64 PPR
              point queries at the ``repro.launch.serve`` defaults; every
              answer must carry its full sample count.
(d) launchers ``repro.launch.walk.main`` and ``repro.launch.serve.main`` at
              their defaults.
(e) 4 chips   ``DistributedWalkEngine`` on a ``(1, 4)`` ("data", "model")
              mesh over a 4-block scale-18 Kronecker graph: the block
              shards must sit on 4 distinct devices, and the endpoint
              counts must equal the in-memory oracle's.

Each phase prints one informational line (wall seconds, steps, steps/s and
the compile-cache directory); these are not measurements to compare.  A
failed check raises and the script exits non-zero.  Without a TPU it exits
non-zero before any phase runs; there is no CPU fallback.  The last line
is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core import (  # noqa: E402
    BiBlockEngine,
    InMemoryWalker,
    partition_into_n_blocks,
    rmat,
    rwnv_task,
)
from repro.io import write_and_open  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

#: node2vec parameters of every batch phase (node2vec paper's p/q grid)
P, Q = 4.0, 0.25
#: phase (b) starts a walk at every BATCH_WALK_STRIDE-th vertex of scale 20
BATCH_WALK_STRIDE = 64


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _report(phase: str, wall: float, steps: int, cache: str) -> None:
    rate = steps / wall if wall > 0 else 0.0
    print(
        f"[info] phase={phase} wall_s={wall} steps={steps} steps_per_s={rate} "
        f"compile_cache={cache}",
        flush=True,
    )


def kronecker(scale: int, blocks: int, *, seed: int = 0):
    """Graph500 Kronecker graph at ``scale`` in ``blocks`` edge-balanced blocks."""
    return partition_into_n_blocks(rmat(scale, 16, 0.57, 0.19, 0.19, seed=seed), blocks)


def phase_bitwise(directory: str, cache: str, *, scale: int = 14, length: int = 20, seed: int = 0):
    """(a): auto and full loading reproduce the oracle's endpoint counts."""
    bg = kronecker(scale, 8, seed=seed)
    task = rwnv_task(p=P, q=Q, walks_per_vertex=1, length=length, seed=seed)
    t0 = time.perf_counter()
    with write_and_open(bg, directory) as disk:
        runs = {
            loading: BiBlockEngine(disk, task, pool="disk", loading=loading).run()
            for loading in ("auto", "full")
        }
    oracle = InMemoryWalker(bg, task).run(record_walks=False)
    wall = time.perf_counter() - t0
    for loading, res in runs.items():
        _require(
            np.array_equal(res.endpoint_counts, oracle.endpoint_counts),
            f"loading={loading!r} endpoint counts differ from the in-memory oracle",
        )
    _require(int(oracle.endpoint_counts.sum()) == bg.num_vertices, "oracle lost walks")
    steps = sum(r.steps_sampled for r in runs.values()) + oracle.steps_sampled
    _report("bitwise", wall, steps, cache)


def phase_batch(disk, cache: str, *, length: int = 80, walk_stride: int = 1, seed: int = 0):
    """(b): node2vec walks from every ``walk_stride``-th vertex, all ended."""
    task = rwnv_task(p=P, q=Q, walks_per_vertex=1, length=length, seed=seed)
    sources = np.arange(0, disk.num_vertices, walk_stride, dtype=np.int64)
    engine = BiBlockEngine(disk, task, pool="disk", block_cache_blocks=4, initial_walks=sources)
    t0 = time.perf_counter()
    res = engine.run()
    wall = time.perf_counter() - t0
    _require(engine.unfinished == 0, f"{engine.unfinished} walks never ended")
    _require(
        int(res.endpoint_counts.sum()) == res.num_walks == sources.size,
        "endpoint histogram does not account for every walk",
    )
    _report("batch", wall, res.steps_sampled, cache)


def phase_serve(disk, cache: str, *, queries: int = 64, seed: int = 0):
    """(c): PPR point queries at the serving launcher's defaults."""
    from repro.serve import QueryConfig, WalkQueryServer

    config = QueryConfig(decay=0.85, samples=32)
    rng = np.random.default_rng(seed + 7)
    hot_lo, hot_hi = int(disk.block_starts[0]), int(disk.block_starts[1])
    t0 = time.perf_counter()
    with WalkQueryServer(disk, max_batch=32, hot_blocks=2, seed=seed) as server:
        for _ in range(queries):
            # Kronecker hubs have the low vertex ids, so block 0 is hot
            if rng.random() < 0.85:
                source = int(rng.integers(hot_lo, hot_hi))
            else:
                source = int(rng.integers(0, disk.num_vertices))
            server.submit(source, config)
        answers = server.flush()
        steps = server.stats.steps_sampled
    wall = time.perf_counter() - t0
    _require(len(answers) == queries, f"{len(answers)} of {queries} queries answered")
    for ans in answers:
        _require(
            ans.num_walks == config.samples and int(ans.counts.sum()) == config.samples,
            f"query {ans.qid} answered with {int(ans.counts.sum())} of {config.samples} samples",
        )
    _report("serve", wall, steps, cache)


def phase_launchers(cache: str, walk_argv=(), serve_argv=()):
    """(d): both CLI entry points, in this process."""
    from repro.launch import serve, walk

    t0 = time.perf_counter()
    results = walk.main(list(walk_argv))
    answers, stats = serve.main(list(serve_argv))
    wall = time.perf_counter() - t0
    for name, res in results.items():
        _require(
            int(res.endpoint_counts.sum()) == res.num_walks,
            f"walk launcher: engine {name} left walks unfinished",
        )
    _require(len(answers) > 0, "serve launcher answered no query")
    steps = sum(r.steps_sampled for r in results.values()) + stats.steps_sampled
    _report("launchers", wall, steps, cache)


def phase_four_chips(cache: str, *, scale: int = 18, length: int = 20, seed: int = 0):
    """(e): the distributed sweep over 4 devices equals the oracle."""
    from jax.sharding import Mesh

    from repro.core.distributed import DistributedWalkEngine

    devices = jax.devices()
    _require(len(devices) >= 4, f"4 devices needed, found {len(devices)}")
    mesh = Mesh(np.array(devices[:4]).reshape(1, 4), ("data", "model"))
    bg = kronecker(scale, 4, seed=seed)
    task = rwnv_task(p=P, q=Q, walks_per_vertex=1, length=length, seed=seed)
    t0 = time.perf_counter()
    engine = DistributedWalkEngine(bg, task, mesh)
    homes = {shard.device for shard in engine.block_shards.indices.addressable_shards}
    _require(len(homes) == 4, f"block shards sit on {len(homes)} device(s), not 4")
    out = engine.run()
    oracle = InMemoryWalker(bg, task).run(record_walks=False)
    wall = time.perf_counter() - t0
    _require(int(out["alive"].sum()) == 0, "distributed sweep left walks alive")
    counts = np.bincount(out["cur"], minlength=bg.num_vertices)
    _require(
        np.array_equal(counts, oracle.endpoint_counts),
        "distributed endpoint counts differ from the in-memory oracle",
    )
    _report("four_chips", wall, int(out["hop"].sum()), cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="1: phases (a)-(d) on one chip; 4: only the distributed sweep",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (platform {devices[0].platform!r}); "
            "this script runs only on the chip",
            file=sys.stderr,
        )
        return 1
    cache = use_compile_cache()
    if args.chips == 4:
        phase_four_chips(cache, seed=args.seed)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            phase_bitwise(str(Path(work) / "bitwise"), cache, seed=args.seed)
            t0 = time.perf_counter()
            bg = kronecker(20, 8, seed=args.seed)
            with write_and_open(bg, str(Path(work) / "batch")) as disk:
                del bg  # the phases read the block file only
                print(f"[info] phase=build_scale20 wall_s={time.perf_counter() - t0}", flush=True)
                phase_batch(disk, cache, walk_stride=BATCH_WALK_STRIDE, seed=args.seed)
                phase_serve(disk, cache, seed=args.seed)
        phase_launchers(cache)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
