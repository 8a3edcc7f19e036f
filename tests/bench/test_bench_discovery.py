"""The harness finds every configuration, traffic mix, mode, result kind and
metric by name, and a new one is a new file plus an entry: no file of the
benchmark changes."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from benchtest_util import BENCH, FOUR_CHIP_CELL, ROOT, add_four_chip_cell, copy_checkout, tiny_run

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _entries_resolve(root: Path) -> None:
    bench = harness.load_benchmark(root)
    bench_dir = root / "bench"
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for cfg in bench["configs"]:
        assert NAME.match(cfg["name"]) and (root / cfg["file"]).is_file()
        assert all(NAME.match(k) for k in cfg["reduced"])
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        harness.load_config(bench, cell, root)
        mode = harness.load_traffic(cell["traffic"], bench_dir)["mode"]
        assert NAME.match(mode) and (bench_dir / "modes" / f"{mode}.py").is_file()
        assert callable(harness.load_mode(mode, bench_dir))
        for trace in (False, True):
            for m in harness.cell_metrics(bench, cell, trace):
                assert callable(harness.load_metric(m["name"], bench_dir))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def _cells_report(root: Path) -> None:
    bench = harness.load_benchmark(root)
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = harness.cell_metrics(bench, cell, True)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)


def _kind_is_a_file(monkeypatch, root: Path, workload: str) -> None:
    kinds = []
    load_kind = harness.load_kind

    def seen(name, bench_dir=BENCH):
        kinds.append(name)
        return load_kind(name, bench_dir)

    monkeypatch.setattr(harness, "load_kind", seen)
    result, _ = tiny_run(workload, root=root)
    assert result["correct"] is True, result["checks"]
    assert kinds and all(NAME.match(k) and (root / "bench" / "kinds" / f"{k}.py").is_file() for k in kinds)


def test_every_entry_resolves_to_its_files():
    _entries_resolve(ROOT)


def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    _cells_report(ROOT)


@pytest.mark.parametrize("workload", [c["name"] for c in harness.load_benchmark()["workloads"]])
def test_the_kind_a_cell_returns_is_a_file(monkeypatch, workload):
    _kind_is_a_file(monkeypatch, ROOT, workload)


@pytest.fixture(scope="module")
def four_chip_checkout(tmp_path_factory):
    """A copied checkout that gains a cell with ``chips`` 4 as new files and
    entries alone (``benchtest_util.add_four_chip_cell``)."""
    root = copy_checkout(tmp_path_factory.mktemp("four_chips"))
    before = _files(root)
    add_four_chip_cell(root)
    assert _changed(root, before) == {Path("BENCHMARK.json")}
    return root


@pytest.mark.parametrize("check", ["entries_resolve", "cells_report", "kind_is_a_file"])
def test_a_four_chip_cell_added_as_files_passes_each_per_cell_check(monkeypatch, four_chip_checkout, check):
    """The checks above, on a checkout with a four-chip cell beside
    ``rwnv.kron20``; this process has one device, the cell's run four."""
    if check == "entries_resolve":
        _entries_resolve(four_chip_checkout)
    elif check == "cells_report":
        _cells_report(four_chip_checkout)
    else:
        _kind_is_a_file(monkeypatch, four_chip_checkout, FOUR_CHIP_CELL)


@pytest.mark.parametrize("folder", ["modes", "kinds", "metrics"])
def test_a_missing_file_is_named(folder):
    load = {"modes": harness.load_mode, "kinds": harness.load_kind, "metrics": harness.load_metric}[folder]
    with pytest.raises(FileNotFoundError, match=f"{folder}/nosuch.py is missing"):
        load("nosuch")


def test_an_unknown_mode_stops_the_run():
    with pytest.raises(FileNotFoundError, match="modes/nosuch.py is missing"):
        tiny_run("rwnv.kron20", traffic_edit=lambda t: t.update(mode="nosuch"))


def test_the_batch_kind_needs_the_recorded_walks():
    with pytest.raises(ValueError, match="record_walks must be on"):
        harness.load_kind("batch")({"corpus": None}, None, {}, None)


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*") if p.is_file()}


def _changed(root: Path, before: dict) -> set:
    after = _files(root)
    return {p for p in before if after[p] != before[p]}


def test_new_cell_mix_and_metric_are_files_plus_entries(tmp_path):
    """A copy of the benchmark gains a traffic mix, a metric and a cell; the
    unchanged harness runs the new cell and reports the new metric."""
    root = copy_checkout(tmp_path)
    before = _files(root)

    (root / "bench" / "traffic" / "rwnv-sparse.json").write_text(
        json.dumps({"mode": "job", "source_stride": 4, "warmup_supersteps": 1})
    )
    (root / "bench" / "metrics" / "ondemand_bytes_per_step.batch.py").write_text(
        'def read(r):\n    return r.per_step("ondemand_bytes")\n'
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "rwnv-sparse.kron20", "config": "kron20-node2vec-corpus", "traffic": "rwnv-sparse",
         "chips": 1, "why": "every 4th vertex"}
    )
    bench["end_to_end"][0]["workloads"].append("rwnv-sparse.kron20")
    bench["per_layer"].append(
        {"name": "ondemand_bytes_per_step.batch", "unit": "B/step", "better": "lower",
         "source": "program_counter", "layer": "block store", "moves": "walk_steps_per_s",
         "workloads": ["rwnv-sparse.kron20"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert _changed(root, before) == {Path("BENCHMARK.json")}

    result, _ = tiny_run("rwnv-sparse.kron20", trace=True, root=root)
    assert result["correct"] is True
    assert "ondemand_bytes_per_step.batch" in result["metrics"]


#: a mode through another entry point: the job's walks in one call of the
#: program's in-memory walker, the graph never written to a file
WHOLE_MODE = '''
import numpy as np

from drivers import Window, filled
from generator import job_sources


def run(graph, config, traffic, seeds, seconds, trace_dir, compiles, log, devices):
    from repro.core.graph import BlockedGraph, CSRGraph
    from repro.core.stats import IOStats
    from repro.core.transition import rwnv_task
    from repro.engines.inmemory import InMemoryWalker

    indptr, indices, starts = graph
    walk, k_max = config["walk"], config["engine"]["k_max"]
    bg = BlockedGraph(CSRGraph(indptr.copy(), indices.copy()), starts)
    sources = job_sources(bg.num_vertices, traffic, walk["walks_per_vertex"])
    task = rwnv_task(p=walk["p"], q=walk["q"], walks_per_vertex=walk["walks_per_vertex"],
                     length=walk["length"], seed=seeds.walk)
    walker = InMemoryWalker(bg, task, k_max=k_max)
    win = Window(IOStats(), compiles, trace_dir)
    win.open()
    res = walker.run(record_walks=True)
    win.stats = res.stats
    win.close()
    return {"kind": "whole", "window": win, "attempted": 1, "failed": 0,
            "corpus": res.corpus, "sources": sources,
            "filled_open": np.ones(len(sources), np.int64), "filled_close": filled(res.corpus),
            "ended": np.arange(len(sources)), "walk": walk, "k_max": k_max}
'''

#: a kind that wraps the batch check with one more number
WHOLE_KIND = '''
from pathlib import Path

import harness

batch = harness.load_kind("batch", Path(__file__).resolve().parents[1])


def check(out, graph, audit, rng, *, control=False):
    missing = abs(out["corpus"].shape[0] - out["sources"].size)
    return batch(out, graph, audit, rng, control=control) + [
        {"name": "missing_walks", "value": int(missing), "limit": 0}
    ]
'''


def test_new_mode_kind_mix_and_cell_are_files_plus_entries(tmp_path):
    """A copy of the benchmark gains a mode, a result kind, a traffic mix and
    a cell; the unchanged harness drives the new entry point and judges it."""
    root = copy_checkout(tmp_path)
    before = _files(root)

    (root / "bench" / "modes" / "whole.py").write_text(WHOLE_MODE)
    (root / "bench" / "kinds" / "whole.py").write_text(WHOLE_KIND)
    (root / "bench" / "traffic" / "rwnv-whole.json").write_text(
        json.dumps({"mode": "whole", "source_stride": 1})
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "rwnv-whole.kron20", "config": "kron20-node2vec-corpus", "traffic": "rwnv-whole",
         "chips": 1, "why": "a walk from every vertex, in memory"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert _changed(root, before) == {Path("BENCHMARK.json")}

    result, lines = tiny_run("rwnv-whole.kron20", root=root)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == {
        "source_mismatch", "unrecorded_steps", "invalid_hops", "bias_z", "short_walks", "missing_walks",
    }
    assert not any(ln.startswith("[setup] block_file_s") for ln in lines)
