"""The harness finds every configuration, traffic mix and metric by name, and
a new one is a new file plus an entry: no file of the benchmark changes."""

from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

from benchtest_util import BENCH, ROOT, tiny_run

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_entry_resolves_to_its_files():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for cfg in bench["configs"]:
        assert NAME.match(cfg["name"]) and (ROOT / cfg["file"]).is_file()
        assert all(NAME.match(k) for k in cfg["reduced"])
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        harness.load_config(bench, cell)
        assert harness.load_traffic(cell["traffic"])["mode"] == "job"
        for trace in (False, True):
            for m in harness.cell_metrics(bench, cell, trace):
                assert callable(harness.load_metric(m["name"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = harness.cell_metrics(bench, cell, True)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)


def test_new_cell_mix_and_metric_are_files_plus_entries(tmp_path):
    """A copy of the benchmark gains a traffic mix, a metric and a cell; the
    unchanged harness runs the new cell and reports the new metric."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p.relative_to(root): p.read_bytes() for p in root.rglob("*") if p.is_file()}

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

    after = {p.relative_to(root): p.read_bytes() for p in root.rglob("*") if p.is_file()}
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {Path("BENCHMARK.json")}

    result, _ = tiny_run("rwnv-sparse.kron20", trace=True, root=root)
    assert result["correct"] is True
    assert "ondemand_bytes_per_step.batch" in result["metrics"]
