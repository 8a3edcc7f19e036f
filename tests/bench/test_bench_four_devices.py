"""A cell on four chips needs no edit of the harness: in a copied checkout, a
cell with ``chips`` 4 and a mode of its own are given four devices, and the
mode builds its ``("data", "model")`` mesh over them.  The run is a
subprocess with four host devices, so that this process keeps one."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchtest_util import ROOT, copy_checkout

TESTS = Path(__file__).resolve().parent

#: the job's walks in sweeps of the program's ``DistributedWalkEngine`` over
#: a (1, n) mesh of the cell's devices; the blocks live in device memory
MESH_MODE = '''
import numpy as np
from jax.sharding import Mesh

from drivers import Window


def run(graph, config, traffic, seeds, seconds, trace_dir, compiles, log, devices):
    from repro.core.distributed import DistributedWalkEngine
    from repro.core.graph import BlockedGraph, CSRGraph
    from repro.core.transition import rwnv_task

    indptr, indices, starts = graph
    walk = config["walk"]
    mesh = Mesh(np.array(devices).reshape(1, len(devices)), ("data", "model"))
    bg = BlockedGraph(CSRGraph(indptr.copy(), indices.copy()), starts)
    task = rwnv_task(p=walk["p"], q=walk["q"], walks_per_vertex=walk["walks_per_vertex"],
                     length=walk["length"], seed=seeds.walk)
    engine = DistributedWalkEngine(bg, task, mesh, k_max=config["engine"]["k_max"])
    win = Window(engine.stats, compiles, trace_dir)
    win.open()
    res = engine.run()
    win.close()
    return {"kind": "mesh", "window": win, "attempted": res["sweeps"], "failed": 0,
            "alive": res["alive"], "given": [d.id for d in devices],
            "mesh_shape": list(mesh.devices.shape), "mesh_axes": list(mesh.axis_names),
            "mesh_devices": [d.id for d in mesh.devices.flat]}
'''

#: every walk of the sweep ran to its end
MESH_KIND = '''
def check(out, graph, audit, rng, *, control=False):
    return [{"name": "unfinished_walks", "value": int(out["alive"].sum()), "limit": 0}]
'''

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
from pathlib import Path
sys.path.insert(0, {tests!r})
from benchtest_util import tiny_run
import harness

seen = []
readings = harness.Readings

def keep(out, *a, **kw):
    seen.append(out)
    return readings(out, *a, **kw)

harness.Readings = keep
result, lines = tiny_run("mesh4.kron10", root=Path({root!r}))
(out,) = seen
print("RESULT " + json.dumps({{
    "correct": result["correct"],
    "count": result["device"]["count"],
    "checks": result["checks"],
    "given": out["given"],
    "mesh_shape": out["mesh_shape"],
    "mesh_axes": out["mesh_axes"],
    "mesh_devices": out["mesh_devices"],
    "setup": [ln.split()[1].split("=")[0] for ln in lines if ln.startswith("[setup]")],
}}))
"""


def test_a_four_chip_cell_gets_four_devices_and_builds_its_mesh(tmp_path):
    root = copy_checkout(tmp_path)
    (root / "bench" / "modes" / "mesh.py").write_text(MESH_MODE)
    (root / "bench" / "kinds" / "mesh.py").write_text(MESH_KIND)
    (root / "bench" / "traffic" / "mesh4.json").write_text(json.dumps({"mode": "mesh"}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (cfg_entry,) = bench["configs"]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    config["blocks"]["count"] = 4  # one block for each rank of the mesh's model axis
    (root / "bench" / "configs" / "kron-4blocks.json").write_text(json.dumps(config))
    bench["configs"].append({**cfg_entry, "name": "kron-4blocks", "file": "bench/configs/kron-4blocks.json"})
    bench["workloads"].append(
        {"name": "mesh4.kron10", "config": "kron-4blocks", "traffic": "mesh4", "chips": 4,
         "why": "the sharded sweep over four devices"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(tests=str(TESTS), root=str(root))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[len("RESULT "):])
    assert out["count"] == 4 and len(set(out["given"])) == 4
    assert out["mesh_shape"] == [1, 4] and out["mesh_axes"] == ["data", "model"]
    assert out["mesh_devices"] == out["given"]
    assert out["correct"] is True, out["checks"]
    assert "block_file_s" not in out["setup"]
