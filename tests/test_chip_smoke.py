"""``chip_smoke.py``'s phases on the CPU at tiny sizes.

The script itself refuses to run without a TPU; these tests drive its phase
functions directly (scale-10 Kronecker graphs, the four-chip phase on four
virtual CPU devices in a subprocess) so a change that breaks the chip's
main path fails here first, at no chip time.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro.io import write_and_open
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """Point the compile cache at a scratch dir through the environment, so
    the launchers' ``use_compile_cache()`` leaves the process config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return str(tmp_path / "jax_cache")


def test_bitwise_phase(tmp_path, cache_env):
    chip_smoke.phase_bitwise(str(tmp_path / "g"), cache_env, scale=10, length=10)


def test_batch_and_serve_phases(tmp_path, cache_env, capsys):
    with write_and_open(chip_smoke.kronecker(10, 8), str(tmp_path / "g")) as disk:
        chip_smoke.phase_batch(disk, cache_env, length=12, walk_stride=2)
        chip_smoke.phase_serve(disk, cache_env, queries=8)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[1] for ln in lines] == ["phase=batch", "phase=serve"]


def test_launcher_phase(cache_env):
    small = ["--vertices", "300", "--blocks", "4", "--length", "6"]
    chip_smoke.phase_launchers(
        cache_env, walk_argv=small, serve_argv=[*small, "--queries", "8"]
    )


def test_four_chip_phase_on_virtual_devices():
    code = (
        "import chip_smoke\n"
        "chip_smoke.phase_four_chips('off', scale=10, length=8)\n"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(REPO),
    }
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "phase=four_chips" in proc.stdout


def test_main_refuses_the_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line, no phase line
    assert "no TPU" in out.err


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

