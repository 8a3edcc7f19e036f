"""Distributed walk engine (shard_map over 8 fake devices) — subprocess
isolated so the main pytest process keeps a single-device jax."""

import json
import os
import subprocess
import sys
from pathlib import Path


SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
import numpy as np, jax
from repro.core import erdos_renyi, partition_into_n_blocks, rwnv_task, prnv_task
from repro.core.distributed import DistributedWalkEngine, ring_owner_and_round

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
g = erdos_renyi(800, 6400, seed=3)
bg = partition_into_n_blocks(g, 4)

out = {{}}

# 1) every walk completes
task = rwnv_task(walks_per_vertex=2, length=8, seed=1)
res = DistributedWalkEngine(bg, task, mesh).run()
out["alive"] = int(res["alive"].sum())
out["complete"] = float((res["hop"] == 8).mean())
out["sweeps"] = res["sweeps"]

# 2) ring schedule covers each unordered pair exactly once per sweep
import jax.numpy as jnp
nb = 4
seen = {{}}
for a in range(nb):
    for b in range(nb):
        if a == b: continue
        o, r = ring_owner_and_round(jnp.int32(a), jnp.int32(b), nb)
        key = (min(a, b), max(a, b))
        seen.setdefault(key, set()).add((int(o), int(r)))
out["pair_unique"] = all(len(v) == 1 for v in seen.values())
out["rounds_within_half"] = all(
    list(v)[0][1] <= nb // 2 for v in seen.values()
)

# 3) second-order restart task also drains
taskq = prnv_task(5, g.num_vertices, samples_per_vertex=1, seed=2)
resq = DistributedWalkEngine(bg, taskq, mesh).run()
out["q_alive"] = int(resq["alive"].sum())

print("RESULT " + json.dumps(out))
"""


def test_distributed_engine_subprocess():
    code = SCRIPT.format(src=SRC)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["alive"] == 0
    assert out["complete"] == 1.0
    assert out["sweeps"] <= 9
    assert out["pair_unique"] and out["rounds_within_half"]
    assert out["q_alive"] == 0


def test_distributed_persists_through_shared_pool(tmp_path):
    """The shard_map driver carries walk state between sweeps through the
    shared :class:`repro.io.ShardedWalkPool` instead of private arrays:
    capacity-limited routing forces a multi-sweep frontier through the
    pool, a disk-backed pool moves real spilled bytes, and — because the
    RNG is counter-based per (walk id, hop) and the drain scatters each
    walk back to its global wid slot — not a single trajectory changes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import erdos_renyi, partition_into_n_blocks, rwnv_task
    from repro.core.distributed import DistributedWalkEngine

    g = erdos_renyi(300, 2400, seed=3)
    bg = partition_into_n_blocks(g, 1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    task = rwnv_task(p=2.0, q=0.5, walks_per_vertex=1, length=6, seed=5)
    keys = ("prev", "cur", "hop", "alive")

    ref = DistributedWalkEngine(bg, task, mesh).run()
    limited = DistributedWalkEngine(bg, task, mesh, capacity_factor=0.1).run()
    assert limited["sweeps"] > ref["sweeps"]  # the frontier really crossed sweeps
    for k in keys:
        np.testing.assert_array_equal(limited[k], ref[k])

    pool_dir = str(tmp_path / "pool")
    disk = DistributedWalkEngine(
        bg, task, mesh, capacity_factor=0.1,
        pool="disk", pool_flush_walks=0, pool_dir=pool_dir, pool_shards=2,
    ).run()
    for k in keys:
        np.testing.assert_array_equal(disk[k], ref[k])
    s = disk["stats"]
    assert s.walk_bytes_written > 0  # real records moved through the pool
    assert sum(s.shard_spill_bytes.values()) == s.walk_bytes_written
    assert not os.path.isdir(pool_dir), "shared pool spill dir leaked"


def test_distributed_single_device_matches_oracle():
    """In-process pin for the distributed sweep (1x1 mesh, one block): the
    wid-carrying routing + counter-based RNG must reproduce the in-memory
    oracle's walks bitwise."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import (
        InMemoryWalker,
        erdos_renyi,
        partition_into_n_blocks,
        rwnv_task,
    )
    from repro.core.distributed import DistributedWalkEngine

    g = erdos_renyi(300, 2400, seed=3)
    bg = partition_into_n_blocks(g, 1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    task = rwnv_task(p=2.0, q=0.5, walks_per_vertex=1, length=6, seed=5)
    out = DistributedWalkEngine(bg, task, mesh).run()
    assert out["alive"].sum() == 0
    oracle = InMemoryWalker(bg, task).run(record_walks=False)
    counts = np.bincount(out["cur"], minlength=g.num_vertices)
    np.testing.assert_array_equal(counts, oracle.endpoint_counts)
