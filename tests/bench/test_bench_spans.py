"""The readers of the program's spans (``bench/metrics/``): each on a
hand-built span log with known shares, ``None`` where the log cannot answer,
and all of them on a whole cell driven on the CPU at a tiny scale."""

from __future__ import annotations

import threading
from types import SimpleNamespace

import pytest

from benchtest_util import tiny_run

import harness

from repro.core.spans import SpanRecorder

T_OPEN, T_CLOSE, SETUP_S = 10.0, 20.0, 10.0

#: the main thread's spans: set-up, then two advance calls and the host work
#: between them; the two gaps [16.8, 17] and [19, 20] are in no span
MAIN = [
    ("advance", 2.0, 6.0, 100),
    ("advance.pack", 10.0, 10.5, 0),
    ("advance.upload", 10.5, 11.0, 0),
    ("compile:jit(f)", 11.0, 11.5, 0),
    ("advance.device", 11.0, 13.0, 0),
    ("advance.fetch", 13.0, 13.5, 0),
    ("advance.record", 13.5, 14.0, 0),
    ("advance", 10.0, 14.0, 8),
    ("blocks.materialize", 14.0, 14.5, 0),
    ("blocks.get_view", 14.0, 15.0, 300),
    ("blocks.schedule", 15.0, 15.2, 300),
    ("pool.acquire", 15.2, 15.7, 24),
    ("pool.push", 15.7, 16.0, 24),
    ("buckets.split", 16.0, 16.3, 24),
    ("slot.route", 16.3, 16.8, 24),
    ("advance.pack", 17.0, 17.25, 0),
    ("advance.upload", 17.25, 17.5, 0),
    ("advance.device", 17.5, 18.5, 0),
    ("advance.fetch", 18.5, 18.75, 0),
    ("advance.record", 18.75, 19.0, 0),
    ("advance", 17.0, 19.0, 16),
]
#: the walk pool's writer splits ahead; no main-thread metric counts it
WRITER = [("buckets.split", 16.8, 20.0, 64), ("pool.apply", 10.0, 20.0, 0)]

EXPECTED = {
    "pack_upload_share": 15.0,
    "fetch_record_share": 15.0,
    "block_view_share": 12.0,
    "pool_wait_share": 8.0,
    "route_share": 8.0,
    "unspanned_share": 12.0,
    "compile_share": 5.0,
    "walks_per_call": 12.0,
    "setup_advance_share": 40.0,
}
#: with the device call's share, these sum to the whole window
PARTITION = (
    "pack_upload_share",
    "fetch_record_share",
    "block_view_share",
    "pool_wait_share",
    "route_share",
    "unspanned_share",
)


def _log(capacity: int = 65536) -> SpanRecorder:
    rec = SpanRecorder(capacity)
    writer = threading.Thread(target=lambda: [rec.add(*s) for s in WRITER], name="walkpool-writer")
    writer.start()
    writer.join()
    for s in MAIN:
        rec.add(*s)
    return rec


def _readings(spans) -> SimpleNamespace:
    window = SimpleNamespace(stats=SimpleNamespace(spans=spans), t_open=T_OPEN, t_close=T_CLOSE)
    return SimpleNamespace(out={"window": window}, window_s=T_CLOSE - T_OPEN, setup_s=SETUP_S)


def test_the_made_up_log_is_a_partition():
    assert sum(EXPECTED[k] for k in PARTITION) + 30.0 == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_made_up_log(name):
    assert harness.load_metric(name)(_readings(_log())) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_when_the_log_starts_after_the_window(name):
    # only the last two records are kept: the rest of both intervals is lost
    assert harness.load_metric(name)(_readings(_log(capacity=2))) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_without_spans(name):
    """A program with no span recorder (``IOStats`` without ``spans``)."""
    r = _readings(None)
    r.out["window"].stats = SimpleNamespace(exec_time=0.0)
    assert harness.load_metric(name)(r) is None


def test_a_traced_run_reports_every_span_metric(monkeypatch):
    seen = []
    readings = harness.Readings

    def keep(*a, **kw):
        r = readings(*a, **kw)
        seen.append(r)
        return r

    monkeypatch.setattr(harness, "Readings", keep)
    result, _ = tiny_run("rwnv.kron20", trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(EXPECTED) <= set(metrics)
    for name in EXPECTED:
        if name != "walks_per_call":
            assert 0.0 <= metrics[name] <= 100.0, (name, metrics[name])
    assert metrics["walks_per_call"] > 0

    (r,) = seen
    win = r.out["window"]
    spans = win.stats.spans
    device = spans.window(win.t_open, win.t_close, spans.thread_of("advance"))["advance.device"]
    total = sum(metrics[k] for k in PARTITION) + 100.0 * device / r.window_s
    assert total == pytest.approx(100.0, abs=0.5)
