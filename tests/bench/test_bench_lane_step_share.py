"""``lane_step_share``: the window's sampled steps over the advance loop's
lanes (the ``n`` of the window's ``advance.fetch`` spans), on a made-up log,
where the log cannot answer, and on a whole cell driven on the CPU at a tiny
scale."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from benchtest_util import tiny_run

import harness

from repro.core.spans import SpanRecorder

T_OPEN, T_CLOSE = 10.0, 20.0

#: two calls in the window (4,096 and 1,024 lane-iterations) and one before
FETCHES = [(5.0, 5.5, 70_000), (12.0, 12.5, 4096), (17.0, 17.5, 1024)]


def _readings(fetches, steps: int, capacity: int = 64):
    rec = SpanRecorder(capacity)
    for t0, t1, n in fetches:
        rec.add("advance.device", t0 - 1.0, t0)
        rec.add("advance.fetch", t0, t1, n)
    window = SimpleNamespace(stats=SimpleNamespace(spans=rec), t_open=T_OPEN, t_close=T_CLOSE)
    return SimpleNamespace(out={"window": window}, counters={"steps_sampled": steps})


def _read(r):
    return harness.load_metric("lane_step_share")(r)


def test_on_a_made_up_log():
    assert _read(_readings(FETCHES, steps=2048)) == pytest.approx(40.0)


@pytest.mark.parametrize(
    "case",
    ["no-spans", "fetch-counts-nothing", "log-starts-after-the-window"],
)
def test_nothing_where_the_log_cannot_answer(case):
    if case == "no-spans":
        r = _readings(FETCHES, steps=2048)
        r.out["window"].stats = SimpleNamespace(exec_time=0.0)
    elif case == "fetch-counts-nothing":
        # a program whose advance.fetch spans carry no lane count
        r = _readings([(t0, t1, 0) for t0, t1, _ in FETCHES], steps=2048)
    else:
        r = _readings(FETCHES, steps=2048, capacity=2)
    assert _read(r) is None


def test_a_traced_tiny_run(monkeypatch):
    seen = []
    readings = harness.Readings

    def keep(*a, **kw):
        r = readings(*a, **kw)
        seen.append(r)
        return r

    monkeypatch.setattr(harness, "Readings", keep)
    result, _ = tiny_run("rwnv.kron20", trace=True)
    assert result["correct"] is True
    share = result["metrics"]["lane_step_share"]["value"]
    assert 0.0 < share <= 100.0

    (r,) = seen
    win = r.out["window"]
    lanes = sum(s.n for s in win.stats.spans.records(win.t_open, win.t_close) if s.name == "advance.fetch")
    assert share == pytest.approx(100.0 * r.counters["steps_sampled"] / lanes)
