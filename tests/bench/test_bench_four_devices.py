"""A cell on four chips needs no edit of the harness: in a copied checkout, a
cell with ``chips`` 4 and a mode of its own (``benchtest_util``'s
:func:`add_four_chip_cell`) are given four devices, and the mode builds its
``("data", "model")`` mesh over them.  :func:`tiny_run` makes the run in a
child process with four host devices, so that this process keeps one."""

from __future__ import annotations

import json

import jax

from benchtest_util import FOUR_CHIP_CELL, add_four_chip_cell, copy_checkout, tiny_run


def test_a_four_chip_cell_gets_four_devices_and_builds_its_mesh(tmp_path):
    root = copy_checkout(tmp_path)
    add_four_chip_cell(root)
    result, lines = tiny_run(FOUR_CHIP_CELL, root=root)
    setup = dict(ln.split()[1].split("=", 1) for ln in lines if ln.startswith("[setup]"))
    mesh = json.loads(setup["mesh"])
    assert result["device"]["count"] == 4 and len(set(mesh["given"])) == 4
    assert mesh["shape"] == [1, 4] and mesh["axes"] == ["data", "model"]
    assert mesh["devices"] == mesh["given"]
    assert result["correct"] is True, result["checks"]
    assert "block_file_s" not in setup


def test_the_test_process_keeps_one_device():
    """The four-device runs above happen in a child; this process, as the
    tier-1 run starts it, has one CPU device."""
    devices = jax.devices()
    assert len(devices) == 1 and devices[0].platform == "cpu"
