"""Readings that set the limits of a cell's checks: the program's numbers and
the control's, over many seeds, in one call.

    python3 bench/readings.py --workload rwnv.kron20 --seeds 11,12,13 --seconds 10

Each seed is a whole run of the cell in a process of its own (its own
graph, set-up, window and reference check, so that each run's device peak is
its own), with the control judged on the same window beside the program
(``control_checks``: the window's sampled hops redrawn first-order,
p = q = 1).  One JSON line per seed; the benchmark's own runs never do this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if len(seeds) > 1:
        for seed in seeds:
            subprocess.run(
                [sys.executable, __file__, "--workload", args.workload, "--seeds", str(seed),
                 "--seconds", str(args.seconds)],
                check=False,
            )
        return 0
    (seed,) = seeds
    harness.ensure_paths()
    bench = harness.load_benchmark()
    cell = harness.find_workload(bench, args.workload)
    config = harness.load_config(bench, cell)
    chips = int(cell["chips"])
    graph = harness.make_graph(config, harness.Seeds(seed), lambda k, v: None, chips=chips)
    devices = harness.require_chip(chips)
    harness.use_compile_cache()
    result = harness.run_cell(
        bench,
        cell,
        seed=seed,
        seconds=args.seconds,
        trace=False,
        devices=devices,
        t_start=T_START,
        config=config,
        graph=graph,
        control=True,
        emit=lambda line: None,
    )
    row = {
        "seed": seed,
        "correct": result["correct"],
        "attempted": result["attempted"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "memory_peak_bytes": result["device"]["memory_peak_bytes"],
        "checks": {k: v["value"] for k, v in result["checks"].items()},
        "control": {k: v["value"] for k, v in result["control_checks"].items()},
        "wall_s": time.perf_counter() - T_START,
    }
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
