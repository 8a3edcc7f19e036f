"""Run one cell of ``BENCHMARK.json`` once and assemble its result line.

Everything that belongs to one configuration, traffic mix, mode, result kind
or metric is a file found by its name:

* ``configs/<config>.json``: the deployment's sizes;
* ``traffic/<traffic>.json``: the mix's parameters, ``mode`` among them;
* ``modes/<mode>.py``: a ``run(graph, config, traffic, seeds, seconds,
  trace_dir, compiles, log, devices)`` that drives the program through one
  window (``drivers.py`` has the machinery every mode shares) and returns
  the result, whose ``kind`` names the check;
* ``kinds/<kind>.py``: a ``check(out, graph, audit, rng, *, control=False)``
  that judges that result against the plain reference, each number beside
  its limit;
* ``metrics/<metric>.py``: a ``read(r)`` that returns a number, or ``None``
  when the run has nothing for it to read.  With ``--trace 1``, ``r.trace``
  holds the device trace's reduction (``tracing.reduce_events``), among it
  ``module_s``: device seconds per jit module, so a reader finds its own
  program's time by the module's name.

A cell runs on ``cell["chips"]`` devices, and so it does in the CPU tests:
``tests/bench/benchtest_util.py`` runs a cell that asks for more devices
than the test process has in a child process with that many host devices.

The harness makes the cell's graph (the benchmark's CSR, which the reference
keeps) and hands it to the mode with the cell's devices; the mode gives it
to the program in the form that entry point reads.  Adding a cell, a mix, a
mode, a kind or a metric adds files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

clock = time.perf_counter


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# -- discovery ----------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def find_config(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(bench: dict, cell: dict, root: Path = ROOT) -> dict:
    return json.loads((root / find_config(bench, cell["config"])["file"]).read_text())


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def _load(folder: str, name: str, function: str, bench_dir: Path):
    path = bench_dir / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, function)


def load_metric(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load("metrics", name, "read", bench_dir)


def load_mode(name: str, bench_dir: Path = BENCH_DIR):
    """The ``run`` function of ``modes/<name>.py``."""
    return _load("modes", name, "run", bench_dir)


def load_kind(name: str, bench_dir: Path = BENCH_DIR):
    """The ``check`` function of ``kinds/<name>.py``."""
    return _load("kinds", name, "check", bench_dir)


def _applies(metric: dict, cell: dict, reported: set) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones, or with ``trace``
    the per-layer ones."""
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or cell["name"] in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if _applies(m, cell, reported)]


# -- the chip -----------------------------------------------------------------
def require_chip(chips: int):
    """The devices to run on; raises :class:`NoChip` off the accelerator."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r}); the benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# -- seeds ----------------------------------------------------------------------
class Seeds:
    """Independent streams from one ``--seed``: graph, walks, audit."""

    def __init__(self, seed: int):
        ss = np.random.SeedSequence(int(seed) & ((1 << 64) - 1))
        graph, walk, self._audit = ss.spawn(3)
        self.graph = int(graph.generate_state(1)[0] & 0x7FFFFFFF)
        self.walk = int(walk.generate_state(1)[0] & 0x3FFFFFFF)

    def audit_rng(self):
        return np.random.default_rng(self._audit)


# -- the system under test --------------------------------------------------------
def make_graph(config: dict, seeds: Seeds, log, *, chips: int | None = None):
    """The cell's graph ``(indptr, indices, block_starts)`` from the seed: in
    this process, or with ``chips`` in a child process that holds the chip
    while it builds (``graph.build_graph_in_child``) and raises
    :class:`NoChip` when it finds too few."""
    import graph

    spec, blocks = config["graph"], config["blocks"]
    if blocks["partition"] != "edge_balanced":
        raise ValueError(f"unknown partition {blocks['partition']!r}")
    t = clock()
    if chips is None:
        out = graph.build_graph(spec, seeds.graph, blocks["count"])
    else:
        try:
            out = graph.build_graph_in_child(spec, seeds.graph, blocks["count"], chips=chips)
        except graph.ChildFailed as e:
            if e.code == 3:
                raise NoChip(f"no TPU, or fewer than {chips} chips (the graph's child process)") from e
            raise
    log("graph_generation_s", clock() - t)
    return out


class SystemUnderTest:
    """The cell's graph written to a block file and opened through the
    program, for a mode whose entry point reads the graph from disk."""

    def __init__(self, config: dict, graph, log):
        from repro.core.graph import BlockedGraph, CSRGraph
        from repro.io import write_and_open

        t = clock()
        indptr, indices, starts = graph
        # the program gets its own copy; the reference keeps the benchmark's
        bg = BlockedGraph(CSRGraph(indptr.copy(), indices.copy()), starts)
        self._dir = tempfile.TemporaryDirectory(prefix="bench_blocks_")
        self.disk = write_and_open(
            bg, self._dir.name, io_coalesce_gap=config["engine"]["io_coalesce_gap"]
        )
        self.block_starts = np.asarray(bg.block_starts)
        self.num_vertices = int(bg.num_vertices)
        del bg
        log("block_file_s", clock() - t)

    def close(self) -> None:
        self.disk.close()
        self._dir.cleanup()


# -- per-layer readings ---------------------------------------------------------------
class Readings:
    """What a metric reader may read: the window's clock, counters, spans,
    trace and the chip's peaks."""

    def __init__(self, out: dict, setup_s: float, trace, peaks: dict, record: bool):
        win = out["window"]
        self.kind = out["kind"]
        self.out = out
        self.setup_s = setup_s
        self.window_s = win.seconds
        self.counters = win.counters
        self.compiles_in_window = win.compiles.in_window
        self.trace = trace
        self.trace_s = win.trace_s
        self.trace_counters = win.trace_counters if win.trace_s else None
        self.peaks = peaks
        self.record = record

    def per_step(self, *fields) -> float | None:
        steps = self.counters["steps_sampled"]
        if steps <= 0:
            return None
        return sum(self.counters[f] for f in fields) / steps


# -- one run ------------------------------------------------------------------------------
def run_cell(
    bench: dict,
    cell: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    t_start: float,
    config: dict | None = None,
    traffic: dict | None = None,
    root: Path = ROOT,
    control: bool = False,
    graph=None,
    emit=print,
) -> dict:
    """Set up, drive the window, read the device, check against the reference,
    and return the result line's object (``checks`` last).  ``graph`` is the
    cell's graph when the caller made it (:func:`make_graph`); otherwise it is
    made here.  With ``control`` the same window is also judged with the
    control in the program's place (``control_checks``); the benchmark's own
    runs never ask for it.  ``devices`` are the cell's chips, which the mode
    is given."""
    import drivers
    import reference
    import tracing

    bench_dir = root / "bench"
    config = load_config(bench, cell, root) if config is None else config
    traffic = load_traffic(cell["traffic"], bench_dir) if traffic is None else traffic
    mode = load_mode(traffic["mode"], bench_dir)

    def log(name, value):
        emit(f"[setup] {name}={value}")

    seeds = Seeds(seed)
    compiles = drivers.CompileCounter()
    if graph is None:
        graph = make_graph(config, seeds, log)
    indptr, indices, _ = graph
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    out = mode(graph, config, traffic, seeds, seconds, trace_dir, compiles, log, devices)
    del graph
    setup_s = out["window"].t_open - t_start
    emit(f"[setup] setup_s={setup_s} compiles={compiles.total - compiles.in_window}")
    peak = memory_peak(devices)
    win = out["window"]
    emit(f"[window] seconds={win.seconds} calls={out['attempted']} compiles={compiles.in_window} "
         + " ".join(f"{k}={v}" for k, v in win.counters.items()))
    reduced = None
    if trace:
        reduced = tracing.reduce_events(tracing.load_events(trace_dir))
        _rmtree(trace_dir)
    check = load_kind(out["kind"], bench_dir)
    t = clock()
    graph = reference.ReferenceGraph(indptr, indices)
    checks = check(out, graph, config["audit"], seeds.audit_rng())
    emit(f"[check] reference_s={clock() - t}")
    correct = all(c["value"] <= c["limit"] for c in checks)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    peaks = _peaks(devices[0])
    r = Readings(out, setup_s, reduced, peaks, config["engine"]["record_walks"])
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        value = load_metric(m["name"], bench_dir)(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(out["attempted"]),
        "failed": int(out.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = out["window"].trace_s
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    if control:
        ctrl = check(out, graph, config["audit"], seeds.audit_rng(), control=True)
        result["control_checks"] = _named(ctrl)
    result["checks"] = _named(checks)
    return result


def _named(checks: list) -> dict:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}


def _peaks(device) -> dict:
    from roofline import peaks_for

    if device.platform != "tpu":
        return {}
    return peaks_for(device.device_kind)


def _rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def use_compile_cache() -> str:
    """The program's own persistent compile cache, for every program built."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    cache = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def ensure_paths() -> None:
    """Put the program and the benchmark's own modules on ``sys.path``."""
    for p in (ROOT / "src", BENCH_DIR):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"the system under test is missing: no {ROOT / 'src' / 'repro'}")

