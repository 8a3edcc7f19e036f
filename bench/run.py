"""Run one cell of the benchmark once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``), whose ``mode``
names the file ``bench/modes/<mode>.py`` that drives the program.  The run
makes the graph from ``--seed``, hands it to the mode (the corpus job
writes it to a block file), warms up, measures for ``--seconds``, and
checks what the window produced against the plain reference, by the
result's kind (``bench/kinds/``).  Earlier lines itemise set-up; the last
line on stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``
(each compared number beside its limit, also the last lines on stderr).

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 3.  The graph is made on the chip by a child process that
exits before this one touches JAX's backend (``bench/graph.py``).  The
compile cache is the program's own (``repro.launch.compile_cache``), inside
the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # TPU runtime logs would otherwise go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    def emit(line):
        print(line, flush=True)

    try:
        harness.ensure_paths()
        bench = harness.load_benchmark()
        cell = harness.find_workload(bench, args.workload)
        config = harness.load_config(bench, cell)
        chips = int(cell["chips"])
        # the child holds the chip while it builds; this process only after
        graph = harness.make_graph(
            config, harness.Seeds(args.seed), lambda k, v: emit(f"[setup] {k}={v}"), chips=chips
        )
        devices = harness.require_chip(chips)
    except (FileNotFoundError, KeyError, harness.NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    emit(f"[setup] compile_cache={harness.use_compile_cache()}")
    result = harness.run_cell(
        bench,
        cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        devices=devices,
        t_start=T_START,
        config=config,
        graph=graph,
        emit=emit,
    )
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
