"""The readers of the program's wall-clock spans (``chipbench/spans.py`` and
the six metrics that use it), on synthetic span records and a synthetic
traced window whose clock runs apart from ``perf_counter``: offset and
slightly faster, so only the mapping through the two ends of
``bench.window`` puts the spans on the device's time."""

from __future__ import annotations

import itertools
from types import SimpleNamespace as NS

import pytest

import chipbench_testcell
from chipbench import harness, spans
from chipbench import trace_reduce as tr
from repro.obs import wall

METRICS = chipbench_testcell.REPO / "chipbench" / "metrics"
MS = 1_000_000                     # ns
T_OPEN, TRACE = 1.0, (3.0, 4.0)    # perf_counter seconds
OFFSET, RATE = 7_000_000_123, 1 + 2e-6


def on_trace(t_ns: float) -> int:
    """The trace's clock at perf_counter ``t_ns``."""
    return int(OFFSET + t_ns * RATE)


class Spans:
    """Synthetic records, times in ms of perf_counter."""

    def __init__(self):
        self.recs = []
        self._ids = itertools.count(1)

    def add(self, name, t0, t1, parent=None, thread=1, **attrs):
        rec = wall.Record(next(self._ids), parent.id if parent else 0, name,
                          int(t0 * MS), int(t1 * MS), thread, attrs)
        self.recs.append(rec)
        return rec

    def tick(self, t0, *, wait=5.0, account=(0.4, 0.2), up=0.1,
             admitted=0, compiled=False):
        """A decode tick from ``t0`` ms: admission, six prep uploads, a
        dispatch with three uploads, sampling, a drain whose wait and copy
        run on another thread, consumption.  Its length is 20 ms."""
        t = self.add("sched.tick", t0, t0 + 20, stepped=4, admitted=admitted,
                     compiled=compiled)
        adm = self.add("engine.admit", t0, t0 + 0.5, t)
        self.add("account", t0, t0 + account[0], adm, what="admission_price")
        # nested accounting counts once
        a = self.add("account", t0 + 1, t0 + 1 + account[1], t, what="h2d")
        self.add("account", t0 + 1, t0 + 1 + account[1] / 2, a, what="poll")
        for k in range(6):
            self.add("bridge.h2d", t0 + 2 + k * up, t0 + 2 + (k + 1) * up, t)
        d = self.add("engine.dispatch", t0 + 3, t0 + 4, t)
        for k in range(3):
            self.add("bridge.h2d", t0 + 3 + k * up, t0 + 3 + (k + 1) * up, d)
        self.add("engine.sample", t0 + 4, t0 + 5, t)
        dr = self.add("engine.drain", t0 + 5, t0 + 6 + wait, t)
        self.add("bridge.wait", t0 + 5.2, t0 + 5.2 + wait, dr, thread=2)
        self.add("bridge.d2h", t0 + 5.2 + wait, t0 + 5.3 + wait, dr, thread=2)
        self.add("engine.consume", t0 + 12, t0 + 13, t)
        return t


def traced_window(ops_ms: list):
    """A reduced trace of the traced stretch, busy over ``ops_ms`` (ms of
    perf_counter), as the trace's own clock records it."""
    h0, h1 = TRACE
    ops = [tr.Op(on_trace(a * MS), on_trace(b * MS), "packed_step", "fusion")
           for a, b in ops_ms]
    return tr.Reduced(window_ns=(on_trace(h0 * 1e9), on_trace(h1 * 1e9)),
                      host_window_s=TRACE, planes=1, busy_ns=0.0, ops=ops)


def run_of(trace):
    return NS(window=NS(t_open=T_OPEN), trace=trace)


def read(name, run):
    return harness._module(METRICS / f"{name}.py").read(run)


NAMES = ("tick_host_ms", "accounting_ms", "bridge_host_ms",
         "crossings_per_tick", "admit_host_ms", "idle_in_bridge_share")


@pytest.fixture
def recorded(monkeypatch):
    """Hand the readers synthetic records instead of the recorder's."""
    box = {"since": 0}

    def use(s: Spans, since: int = 0):
        box.update(recs=s.recs, since=since)

    monkeypatch.setattr(spans, "recorded",
                        lambda: (box["recs"], box["since"]))
    return use


def untraced_spans() -> Spans:
    s = Spans()
    s.tick(1100, wait=5.0, account=(0.4, 0.2))
    s.tick(1200, wait=7.0, account=(0.6, 0.4))
    s.tick(1300, wait=6.0, account=(0.5, 0.2))
    # an admitting tick, a compiling tick, and a tick before the window
    # opened: none is a decode-only tick of the untraced stretch
    adm = s.tick(1400, wait=1.0, admitted=1)
    p = s.add("engine.prefill", 1400.5, 1404.5, adm, req="r1", prompt_len=64)
    s.add("bridge.h2d", 1400.6, 1400.8, p)
    s.add("bridge.wait", 1403, 1404, p)
    s.add("bridge.d2h", 1404, 1404.1, p)
    p2 = s.add("engine.prefill", 1500, 1506, None, req="r2", prompt_len=64)
    s.add("bridge.wait", 1501, 1501.5, p2)
    s.tick(1600, compiled=True)
    s.tick(900)
    # a tick inside the traced stretch is not read by the span-only metrics
    s.tick(3100, wait=50.0)
    return s


def test_span_metrics_on_decode_ticks(recorded):
    recorded(untraced_spans())
    run = run_of(traced_window([]))
    # tick less its wait: 20 - 5, 20 - 7, 20 - 6 -> median 14
    assert read("tick_host_ms", run) == pytest.approx(14.0)
    # admission price + the outer h2d account: 0.6, 1.0, 0.7
    assert read("accounting_ms", run) == pytest.approx(0.7)
    # nine uploads of 0.1 and one copy of 0.1
    assert read("bridge_host_ms", run) == pytest.approx(1.0)
    assert read("crossings_per_tick", run) == 10
    # prefills: 4 - 1 and 6 - 0.5
    assert read("admit_host_ms", run) == pytest.approx(4.25)


def test_idle_in_bridge_share_through_both_window_ends(recorded):
    s = Spans()
    s.add("bridge.h2d", 3050, 3080)     # idle all through: 30 ms
    s.add("bridge.d2h", 3200, 3300)     # device busy: nothing
    s.add("bridge.h2d", 3550, 3650)     # 50 ms of it idle
    s.add("engine.sample", 3020, 3090)  # not a crossing
    recorded(s)
    # busy 3.1-3.5 s and 3.6-3.9 s: idle 3.0-3.1, 3.5-3.6, 3.9-4.0
    run = run_of(traced_window([(3100, 3500), (3600, 3900)]))
    assert read("idle_in_bridge_share", run) == pytest.approx(
        100 * 80 / 300, rel=1e-4)
    split = spans.idle_split(run.trace, spans.window(run, traced=True))
    assert sum(split.values()) == pytest.approx(300 * MS * RATE, rel=1e-6)
    # innermost span at each moment of idle time
    assert split["bridge.h2d"] == pytest.approx(80 * MS * RATE, rel=1e-4)
    assert split["engine.sample"] == pytest.approx(40 * MS * RATE, rel=1e-4)
    assert split["-"] == pytest.approx(180 * MS * RATE, rel=1e-4)
    # the two ends of the window recover the offset and the rate
    assert spans.to_trace_ns(run.trace, int(3.05e9)) == pytest.approx(
        on_trace(3.05e9), abs=2)


def test_nothing_to_read(recorded, monkeypatch):
    recorded(untraced_spans())
    no_trace = run_of(None)
    for name in NAMES:
        assert read(name, no_trace) is None
    # the ring let go of spans the untraced stretch started with, and
    # then of spans the traced one did
    recorded(untraced_spans(), since=int(1.5e9))
    for name in NAMES[:-1]:
        assert read(name, run_of(traced_window([]))) is None
    # the tick of the traced stretch: ten crossings of 0.1 ms in 1 s idle
    assert read("idle_in_bridge_share", run_of(traced_window([]))) == \
        pytest.approx(0.1, rel=1e-4)
    recorded(untraced_spans(), since=int(3.5e9))
    for name in NAMES:
        assert read(name, run_of(traced_window([]))) is None
    # no decode-only tick, no admission, no idle time
    recorded(Spans())
    for name in NAMES[:-1]:
        assert read(name, run_of(traced_window([]))) is None
    assert read("idle_in_bridge_share",
                run_of(traced_window([(3000, 4000)]))) is None
    # a program without the recorder
    monkeypatch.setattr(spans, "recorded", lambda: None)
    for name in NAMES:
        assert read(name, run_of(traced_window([]))) is None


def test_the_programs_own_recorder_is_read(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "1")
    wall.refresh()
    wall.clear()
    try:
        with wall.span("sched.tick", stepped=1, admitted=0, compiled=False):
            with wall.span("bridge.h2d"):
                pass
        recs, since = spans.recorded()
        assert [r.name for r in recs] == ["bridge.h2d", "sched.tick"]
        assert since == 0
    finally:
        monkeypatch.undo()
        wall.refresh()
        wall.clear()
