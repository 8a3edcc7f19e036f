"""Shared set-up of the benchmark's tests: its modules on the path, and one
cell driven end to end on the CPU at a tiny scale, the chip check skipped."""

from __future__ import annotations

import shutil
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


def copy_checkout(tmp_path: Path) -> Path:
    """A checkout holding a copy of the benchmark alone (``bench/`` and
    ``BENCHMARK.json``), for a test that adds files and entries to it."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def tiny_run(workload: str, *, seed: int = 7, seconds: float = 0.5, trace: bool = False,
             root: Path = ROOT, traffic_edit=None, control: bool = False):
    """One run of ``workload`` on the CPU at :data:`TINY_SCALE`."""
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
    lines = []
    result = harness.run_cell(
        bench,
        cell,
        seed=seed,
        seconds=seconds,
        trace=trace,
        devices=jax.devices()[: cell["chips"]],
        t_start=time.perf_counter(),
        config=config,
        traffic=traffic,
        root=root,
        control=control,
        emit=lines.append,
    )
    return result, lines
