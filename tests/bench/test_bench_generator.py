"""The traffic generator: the walks' sources of a job."""

from __future__ import annotations

from benchtest_util import BENCH  # noqa: F401  (puts the benchmark on the path)

from generator import job_sources


def test_job_sources_stride():
    assert job_sources(10, {"source_stride": 3}, 2).tolist() == [0, 0, 3, 3, 6, 6, 9, 9]
