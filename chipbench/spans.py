"""The program's wall-clock spans (``repro.obs.wall``), read for the
per-layer metrics of the host's time inside a step.

The recorder hands out raw records: name, ``perf_counter_ns`` at entry and
exit, the span open around it (a thread that works for a span opened on
another one hangs its spans below that span) and attrs.  Everything that
turns them into numbers is here, so the program cannot change how a metric
is computed.

- ``window``: the records of a stretch of the measured window.  The
  span-only metrics read its untraced part, from the window's opening to
  the start of the profiler trace: Python tracing inside the traced part
  slows the host.  Where the program has no recorder, or its ring has let
  go of a span of the stretch, there is nothing to read (``None``).
- ``Tree``: each span's children, and the time a span spends inside spans
  of given names (the outermost of them, so nested ones count once).
- ``to_trace_ns``: ``perf_counter`` onto the trace's clock, linearly
  through the two ends of the harness's ``bench.window`` span, stamped on
  both clocks back to back when the trace starts and stops.
- ``idle_split``: the traced window's device idle time by the innermost
  program span the host was in.

A decode-only tick is a ``sched.tick`` that stepped, admitted nothing and
compiled nothing.
"""

from __future__ import annotations

from collections import defaultdict
from statistics import median
from typing import Optional

TICK = "sched.tick"
PREFILL = "engine.prefill"
ACCOUNT = "account"
WAIT = "bridge.wait"
CROSSINGS = ("bridge.h2d", "bridge.d2h")


def recorded() -> Optional[tuple]:
    """(records, since_ns) of the program's recorder; ``None`` where the
    program has none."""
    try:
        from repro.obs import wall
    except ImportError:
        return None
    return wall.records(), wall.since_ns()


def window(run, *, traced: bool = False) -> Optional[list]:
    """The records of spans wholly inside the run's untraced stretch of the
    window (``traced``: those that overlap the traced stretch); ``None``
    with no trace, no recorder, or a stretch the ring no longer holds
    whole."""
    if run.trace is None:
        return None
    got = recorded()
    if got is None:
        return None
    recs, since = got
    trace_start, trace_stop = run.trace.host_window_s
    lo, hi = ((trace_start, trace_stop) if traced
              else (run.window.t_open, trace_start))
    lo_ns, hi_ns = int(lo * 1e9), int(hi * 1e9)
    if hi_ns <= lo_ns or since > lo_ns:
        return None
    if traced:
        return [r for r in recs if lo_ns < r.end_ns and r.start_ns < hi_ns]
    return [r for r in recs if lo_ns <= r.start_ns and r.end_ns <= hi_ns]


class Tree:
    """Records indexed by their parent."""

    def __init__(self, recs: list):
        self.recs = recs
        self.kids = defaultdict(list)
        for r in recs:
            self.kids[r.parent].append(r)

    def named(self, name: str) -> list:
        return [r for r in self.recs if r.name == name]

    def inside(self, rec, names) -> list:
        """The outermost spans below ``rec`` named one of ``names``."""
        out, todo = [], list(self.kids[rec.id])
        while todo:
            r = todo.pop()
            if r.name in names:
                out.append(r)
            else:
                todo.extend(self.kids[r.id])
        return out

    def time_in(self, rec, names) -> int:
        """Nanoseconds ``rec`` spends inside spans named one of ``names``."""
        return sum(r.end_ns - r.start_ns for r in self.inside(rec, names))

    def count(self, rec, names) -> int:
        """Spans below ``rec`` named one of ``names``, nested ones too."""
        n, todo = 0, list(self.kids[rec.id])
        while todo:
            r = todo.pop()
            n += r.name in names
            todo.extend(self.kids[r.id])
        return n


def decode_ticks(tree: Tree) -> list:
    return [r for r in tree.named(TICK)
            if r.attrs.get("stepped", 0) > 0 and not r.attrs.get("admitted")
            and not r.attrs.get("compiled")]


def median_ms(values: list) -> Optional[float]:
    return median(values) / 1e6 if values else None


def to_trace_ns(trace, t_ns: int) -> float:
    """A ``perf_counter_ns`` time on the trace's clock."""
    (h0, h1), (w0, w1) = trace.host_window_s, trace.window_ns
    return w0 + (t_ns / 1e9 - h0) * (w1 - w0) / (h1 - h0)


def _union(ivs) -> list:
    out = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def idle(trace) -> list:
    """The traced window's intervals (trace ns) in which no op ran on the
    first device."""
    w0, w1 = trace.window_ns
    edges = [w0] + [x for iv in _union((o.start, o.end) for o in trace.ops)
                    for x in iv] + [w1]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def idle_split(trace, recs: list) -> dict:
    """Device idle nanoseconds of the traced window by the innermost
    (shortest) program span open on the host, on any thread (``-``: none).
    ``recs``: the records of the traced stretch."""
    marks = []                       # (time, 0 close / 1 open, record)
    for r in recs:
        marks.append((to_trace_ns(trace, r.start_ns), 1, r))
        marks.append((to_trace_ns(trace, r.end_ns), 0, r))
    marks.sort(key=lambda m: (m[0], -m[1]))    # opens first at a tie
    out = defaultdict(float)
    active = {}
    k = 0
    for a, b in idle(trace):
        # open and close the spans up to each piece of the gap
        t = a
        while True:
            while k < len(marks) and marks[k][0] <= t:
                _, opens, r = marks[k]
                if opens:
                    active[r.id] = r
                else:
                    active.pop(r.id, None)
                k += 1
            nxt = min(b, marks[k][0]) if k < len(marks) else b
            if nxt > t:
                inner = min(active.values(),
                            key=lambda r: r.end_ns - r.start_ns, default=None)
                out[inner.name if inner else "-"] += nxt - t
            if nxt >= b:
                break
            t = nxt
    return dict(out)


def idle_in(trace, recs: list, names) -> Optional[float]:
    """Share of the traced window's device idle time, in percent, in which
    the host was inside a span named one of ``names`` (on any thread)."""
    total = sum(b - a for a, b in idle(trace))
    if total <= 0:
        return None
    spans = _union((to_trace_ns(trace, r.start_ns),
                    to_trace_ns(trace, r.end_ns))
                   for r in recs if r.name in names)
    both, j = 0.0, 0
    for a, b in idle(trace):
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        i = j
        while i < len(spans) and spans[i][0] < b:
            both += max(0.0, min(b, spans[i][1]) - max(a, spans[i][0]))
            i += 1
    return 100.0 * both / total
