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
    assert r["module_s"] == {"jit_pair_advance_impl": pytest.approx(30.0), "jit_other": pytest.approx(2.0)}
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


def test_module_seconds_of_a_recorded_chip_trace():
    """``module_s`` names the advance's module without its hash and sums it
    as ``advance_s`` does; the other readings are those the reduction gave
    before ``module_s`` existed."""
    events = [tuple(e) for e in json.loads((DATA / "trace_sample.json").read_text())]
    r = tracing.reduce_events(events)
    assert r["module_s"] == {"jit_pair_advance_impl": pytest.approx(0.182537481)}
    assert r["advance_s"] == pytest.approx(sum(s for m, s in r["module_s"].items() if "pair_advance" in m))
    assert r["busy_s"] == pytest.approx(0.00558147)
    assert r["devices"] == 1
    assert r["device_ops"][:3] == [
        ["%while.194", pytest.approx(0.004192017)],
        ["%fusion.150", pytest.approx(0.00418871)],
        ["%while.195", pytest.approx(0.001167241)],
    ]
    assert [g[0] for g in r["idle_gaps"]] == ["bench:advance"] * 10
    assert r["idle_gaps"][0][1] == pytest.approx(7.47e-07)


def test_module_seconds_average_over_device_planes():
    """Two chips run the same sharded program: each module's device time is
    the mean over the planes, and the hash after its name is dropped."""
    dev1 = "/device:TPU:1"
    events = [
        (DEV, "XLA Ops", "fusion.1", 0.0, 4e9),
        (dev1, "XLA Ops", "fusion.1", 0.0, 6e9),
        (DEV, "XLA Modules", "jit_sweep(11)", 0.0, 4e9),
        (dev1, "XLA Modules", "jit_sweep(11)", 0.0, 6e9),
        (DEV, "XLA Modules", "jit_sweep(11)", 10e9, 2e9),
        (DEV, "XLA Modules", "jit_pair_advance_impl(3)", 20e9, 1e9),
        (dev1, "XLA Modules", "jit_pair_advance_impl(3)", 20e9, 3e9),
    ]
    r = tracing.reduce_events(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(5.0)
    assert r["module_s"] == {"jit_sweep": pytest.approx(6.0), "jit_pair_advance_impl": pytest.approx(2.0)}
    assert r["advance_s"] == pytest.approx(r["module_s"]["jit_pair_advance_impl"])


def test_load_events_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:advance"):
        jnp.ones(8).sum().block_until_ready()
    tracing.stop()
    events = tracing.load_events(str(tmp_path))
    assert any(e[2] == "bench:advance" for e in events)
