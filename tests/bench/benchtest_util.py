"""Shared set-up of the benchmark's tests: its modules on the path, and one
cell driven end to end on the CPU at a tiny scale, the chip check skipped.

A cell runs on as many devices as it asks for (``chips``).  One that asks
for more than this process has runs in a child process with that many host
devices (this file run as a script), so that the test process keeps its one.

    python3 tests/bench/benchtest_util.py < request.json
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (ROOT / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

#: Kronecker scale of the CPU runs: 1,024 vertices, about 20,000 edges
TINY_SCALE = 10

#: the last line of the child's stdout: ``RESULT_TAG`` + JSON
RESULT_TAG = "TINY_RUN "

#: how long a child's tiny run may take
CHILD_TIMEOUT_S = 600


def copy_checkout(tmp_path: Path) -> Path:
    """A checkout holding a copy of the benchmark alone (``bench/`` and
    ``BENCHMARK.json``), for a test that adds files and entries to it."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def tiny_run(workload: str, *, seed: int = 7, seconds: float = 0.5, trace: bool = False,
             root: Path = ROOT, traffic_edit=None, control: bool = False):
    """One run of ``workload`` on the CPU at :data:`TINY_SCALE`, on the
    cell's ``chips`` devices: ``(result, lines)``.

    ``traffic_edit`` edits the mix here, before the run.  A run on more
    devices than this process has is made in a child process; the kinds it
    loaded are then loaded again here through ``harness.load_kind``, so that
    a test watching that function sees them.  No other patch of this process
    reaches the child."""
    import jax

    bench = harness.load_benchmark(root)
    cell = harness.find_workload(bench, workload)
    config = harness.load_config(bench, cell, root)
    config["graph"]["scale"] = TINY_SCALE
    traffic = harness.load_traffic(cell["traffic"], root / "bench")
    # 128 walks at the tiny scale, as the chip's cell has 131,072 at its own
    traffic["source_stride"] = min(int(traffic.get("source_stride", 1)), 8)
    if traffic_edit is not None:
        traffic_edit(traffic)
    request = {"workload": workload, "seed": seed, "seconds": seconds, "trace": trace,
               "root": str(root), "control": control, "config": config, "traffic": traffic}
    if cell["chips"] <= len(jax.devices()):
        return _run_here(request)
    out = _run_in_child(request, cell["chips"])
    for kind in out["kinds"]:
        harness.load_kind(kind, root / "bench")
    return out["result"], out["lines"]


def _run_here(request: dict):
    import jax

    root = Path(request["root"])
    bench = harness.load_benchmark(root)
    cell = harness.find_workload(bench, request["workload"])
    lines = []
    result = harness.run_cell(
        bench,
        cell,
        seed=request["seed"],
        seconds=request["seconds"],
        trace=request["trace"],
        devices=jax.devices()[: cell["chips"]],
        t_start=time.perf_counter(),
        config=request["config"],
        traffic=request["traffic"],
        root=root,
        control=request["control"],
        emit=lines.append,
    )
    return result, lines


def _run_in_child(request: dict, chips: int) -> dict:
    """The run in a child process with ``chips`` host devices: its result,
    setup lines and the kinds it loaded."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={chips}")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": " ".join(flags)}
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())],
        input=json.dumps(request), env=env, capture_output=True, text=True,
        timeout=CHILD_TIMEOUT_S,
    )
    tagged = [ln for ln in proc.stdout.splitlines() if ln.startswith(RESULT_TAG)]
    if proc.returncode != 0 or len(tagged) != 1:
        raise RuntimeError(
            f"the {chips}-device run of {request['workload']!r} exited with code "
            f"{proc.returncode}:\n{proc.stderr[-4000:]}"
        )
    return json.loads(tagged[0][len(RESULT_TAG):])


def _child() -> int:
    request = json.loads(sys.stdin.read())
    kinds = []
    load_kind = harness.load_kind

    def seen(name, bench_dir=BENCH):
        kinds.append(name)
        return load_kind(name, bench_dir)

    harness.load_kind = seen
    result, lines = _run_here(request)
    print(RESULT_TAG + json.dumps({"result": result, "lines": lines, "kinds": kinds}), flush=True)
    return 0


# -- a cell on four chips, added to a copied checkout as files and entries ----
#: the cell that :func:`add_four_chip_cell` adds
FOUR_CHIP_CELL = "mesh4.kron10"

MESH_MODE = '''
"""``mesh``: the walks of ``rwnv_task`` in sweeps of the program's
``DistributedWalkEngine`` over a (1, n) ``("data", "model")`` mesh of the
cell's devices, the blocks in device memory."""
import json

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
    log("mesh", json.dumps({"given": [d.id for d in devices], "shape": list(mesh.devices.shape),
                            "axes": list(mesh.axis_names),
                            "devices": [d.id for d in mesh.devices.flat]}, separators=(",", ":")))
    bg = BlockedGraph(CSRGraph(indptr.copy(), indices.copy()), starts)
    task = rwnv_task(p=walk["p"], q=walk["q"], walks_per_vertex=walk["walks_per_vertex"],
                     length=walk["length"], seed=seeds.walk)
    engine = DistributedWalkEngine(bg, task, mesh, k_max=config["engine"]["k_max"])
    win = Window(engine.stats, compiles, trace_dir)
    win.open()
    res = engine.run()
    win.close()
    return {"kind": "mesh", "window": win, "attempted": res["sweeps"], "failed": 0,
            "alive": res["alive"], "steps": int(res["hop"].sum()), "chips": len(devices)}
'''

#: every walk of the sweep ran to its end
MESH_KIND = '''
def check(out, graph, audit, rng, *, control=False):
    return [{"name": "unfinished_walks", "value": int(out["alive"].sum()), "limit": 0}]
'''

#: a reader that finds its program's device time by the jit module's name,
#: over the bandwidth of every chip of the cell
SWEEP_ROOFLINE = '''
"""Sharded sweep: share (%) of the bytes-bound roofline of the cell's chips:
the walks' hops times the bytes of a step over the HBM bandwidth of every
chip, against the device time of the sweep's jit module."""

from roofline import advance_roofline_pct


def read(r):
    if r.trace is None or not r.peaks:
        return None
    sweep_s = sum(s for name, s in r.trace["module_s"].items() if "sweep" in name)
    return advance_roofline_pct(r.out["steps"], sweep_s, record=False,
                                hbm_bytes_per_s=r.peaks["hbm_bytes_per_s"], chips=r.out["chips"])
'''


def add_four_chip_cell(root: Path) -> None:
    """Add to the checkout at ``root`` the cell :data:`FOUR_CHIP_CELL`, with
    ``chips`` 4, as new files and entries alone: a configuration with 4 blocks
    (one for each rank of the mesh's model axis), the ``mesh`` mode and kind,
    a mix, and the ``sweep_roofline`` reader; the cell joins
    ``walk_steps_per_s``'s cells."""
    bench_dir = root / "bench"
    (bench_dir / "modes" / "mesh.py").write_text(MESH_MODE)
    (bench_dir / "kinds" / "mesh.py").write_text(MESH_KIND)
    (bench_dir / "metrics" / "sweep_roofline.py").write_text(SWEEP_ROOFLINE)
    (bench_dir / "traffic" / "mesh4.json").write_text(json.dumps({"mode": "mesh"}))
    bench = harness.load_benchmark(root)
    (cfg_entry,) = bench["configs"]
    config = json.loads((root / cfg_entry["file"]).read_text())
    config["blocks"]["count"] = 4
    (bench_dir / "configs" / "kron-4blocks.json").write_text(json.dumps(config))
    bench["configs"].append({**cfg_entry, "name": "kron-4blocks", "file": "bench/configs/kron-4blocks.json"})
    bench["workloads"].append(
        {"name": FOUR_CHIP_CELL, "config": "kron-4blocks", "traffic": "mesh4", "chips": 4,
         "why": "the sharded sweep over four devices"}
    )
    for m in bench["end_to_end"]:
        if m["name"] == "walk_steps_per_s":
            m["workloads"].append(FOUR_CHIP_CELL)
    bench["per_layer"].append(
        {"name": "sweep_roofline", "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "sharded sweep", "moves": "walk_steps_per_s", "workloads": [FOUR_CHIP_CELL]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


if __name__ == "__main__":
    sys.exit(_child())
