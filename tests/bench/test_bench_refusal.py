"""Off the TPU, and without the program beside it, the command prints no
result and exits non-zero."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from benchtest_util import BENCH, ROOT

import harness


def _run(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rwnv.kron20", "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_off_the_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_require_chip_raises_on_cpu():
    import pytest

    with pytest.raises(harness.NoChip):
        harness.require_chip(1)


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
