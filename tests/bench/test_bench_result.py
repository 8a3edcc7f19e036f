"""The result line of a run, and faults planted under the timed path that
must turn ``correct`` false.  Each run drives a whole cell on the CPU at a
tiny scale; only the look for a chip is skipped."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchtest_util import tiny_run

from repro.engines.base import EngineBase
from repro.core.walk import WalkBatch

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _shape(result: dict, e2e: set) -> None:
    line = json.loads(json.dumps(result))
    keys = list(line)
    assert keys[: len(KEYS)] == KEYS and keys[-1] == "checks"
    assert set(line["metrics"]) == e2e
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_batch_line_shape_and_correct():
    result, lines = tiny_run("rwnv.kron20")
    _shape(result, {"walk_steps_per_s", "setup_s"})
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    setup = [ln.split()[1].split("=")[0] for ln in lines if ln.startswith("[setup]")]
    assert setup[:4] == ["graph_generation_s", "block_file_s", "engine_s", "warmup_s"]


def test_fault_state_unchanged_is_not_correct(monkeypatch):
    """An advance that returns its walks unmoved but counts their steps."""

    def unmoved(self, batch, wid, alive=None):
        self.stats.steps_sampled += len(batch)
        return batch, np.ones(len(batch), bool) if alive is None else alive

    monkeypatch.setattr(EngineBase, "_advance", unmoved)
    result, _ = tiny_run("rwnv.kron20")
    assert result["correct"] is False
    assert result["checks"]["unrecorded_steps"]["value"] > 0


@pytest.mark.parametrize("workload", ["rwnv.kron20"])
def test_fault_altered_vertex_is_not_correct(monkeypatch, workload):
    """One vertex of every advance's output replaced where it is produced."""
    advance = EngineBase._advance

    def altered(self, batch, wid, alive=None):
        out, still = advance(self, batch, wid, alive)
        if self.corpus is not None and len(wid):
            row = self.corpus[wid[0]]
            last = int((row >= 0).sum()) - 1
            if last > 0:
                row[last] = (row[last] + 1) % self.bg.num_vertices
        return out, still

    monkeypatch.setattr(EngineBase, "_advance", altered)
    result, _ = tiny_run(workload, seconds=1.0)
    assert result["correct"] is False
    assert result["checks"]["invalid_hops"]["value"] > 0


def test_fault_half_the_batch_left_out_is_not_correct(monkeypatch):
    """An advance that moves the first half of each batch and retires the
    other half where it stands."""
    advance = EngineBase._advance

    def half(self, batch, wid, alive=None):
        k = (len(batch) + 1) // 2
        if k == len(batch):
            return advance(self, batch, wid, alive)
        head, tail = batch.select(slice(0, k)), batch.select(slice(k, None))
        moved, still = advance(self, head, wid[:k], None if alive is None else alive[:k])
        return WalkBatch.concat([moved, tail]), np.concatenate([still, np.zeros(len(tail), bool)])

    monkeypatch.setattr(EngineBase, "_advance", half)
    result, _ = tiny_run("rwnv.kron20")
    assert result["correct"] is False
    assert result["checks"]["short_walks"]["value"] > 0
