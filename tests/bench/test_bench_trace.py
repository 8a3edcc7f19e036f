"""The reduction from a device trace to the per-layer numbers."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchtest_util import BENCH  # noqa: F401  (puts the benchmark on the path)

import tracing

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def test_reduction_of_a_made_up_trace():
    events = [
        (DEV, "XLA Ops", "fusion.1", 0.0, 10e9),
        (DEV, "XLA Ops", "fusion.2", 5e9, 10e9),  # overlaps the first
        (DEV, "XLA Ops", "fusion.1", 20e9, 10e9),
        (DEV, "XLA Ops", "copy.3", 40e9, 2e9),
        (DEV, "XLA Modules", "jit_pair_advance_impl(7)", 0.0, 30e9),
        (DEV, "XLA Modules", "jit_other(2)", 40e9, 2e9),
        (HOST, "python", "bench:advance", 14e9, 8e9),
        (HOST, "python", "bench:flush", 0.0, 45e9),
    ]
    r = tracing.reduce_events(events)
    assert r["busy_s"] == pytest.approx(27.0)  # [0,15] + [20,30] + [40,42]
    assert r["advance_s"] == pytest.approx(30.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(20.0)]
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(10.0), pytest.approx(5.0)]
    # the 15-20 gap lies inside an advance call, the 30-40 gap only in a flush
    assert [g[0] for g in r["idle_gaps"]] == ["bench:flush", "bench:advance"]


def test_no_device_op_gives_nothing():
    assert tracing.reduce_events([(HOST, "python", "bench:advance", 0.0, 1.0)]) is None


def test_reduction_of_a_recorded_chip_trace():
    """Events recorded from a traced window on a TPU v5e (the first few
    thousand device events and the benchmark's spans)."""
    events = [tuple(e) for e in json.loads((DATA / "trace_sample.json").read_text())]
    r = tracing.reduce_events(events)
    ops = [e for e in events if e[1] == "XLA Ops"]
    span = (max(e[3] + e[4] for e in ops) - min(e[3] for e in ops)) / 1e9
    assert 0 < r["busy_s"] <= span
    assert r["advance_s"] > 0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True)


def test_load_events_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:advance"):
        jnp.ones(8).sum().block_until_ready()
    tracing.stop()
    events = tracing.load_events(str(tmp_path))
    assert any(e[2] == "bench:advance" for e in events)
