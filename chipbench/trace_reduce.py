"""From a profiler trace (``.xplane.pb``) to device time per program and
per op kind, the device's busy time, and its idle gaps named by what the
host was doing.

The traced window is the harness's own ``bench.window`` host span (the
whole recorded extent of the host events, where there is none).  On each
TPU device plane the ``XLA Ops`` line holds one event per executed
operation and the ``XLA Modules`` line one per program run; an operation
belongs to the program run whose interval holds it.  Busy time is the
union of the operation intervals inside the window, averaged over the
device planes.  A program run's event names the program and its id
(``jit_step(4130390087658909887)``).  An op event carries only its HLO
text, by which it is named for the breakdown (kind and output shape); the
op's metadata in the device plane adds the scope it was traced under (the
``tf_op`` stat, e.g. ``jit(step)/KERNEL_x/dot_general``), read from the
file by ``op_scopes``.  An idle gap is named by the innermost ``bench.*``
span and the innermost other host event that hold its midpoint.
"""

from __future__ import annotations

import glob
import re
import shutil
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import jax

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ops that run other ops inside them: left out of the breakdown, whose
#: times would otherwise count the inner ops twice
CONTAINERS = ("while", "conditional", "call")


def _interval_union(ivs: list) -> list:
    out = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def module_name(event_name: str) -> str:
    """``jit_packed_step(123)`` -> ``packed_step``."""
    name = re.sub(r"\(-?\d+\)$", "", event_name)
    return re.sub(r"^(jit_|pjit_)", "", name)


def program_id(event_name: str) -> int:
    """``jit_packed_step(123)`` -> 123; 0 where the name has no id."""
    m = re.search(r"\((-?\d+)\)$", event_name)
    return int(m.group(1)) if m else 0


# -- the metadata of a device plane, read from the protobuf wire format ----------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map), stat_metadata
# = 5 (map); map entries: key = 1, value = 2; XEventMetadata: name = 2,
# stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# uint64 = 3, int64 = 4, str = 5, ref = 7.

def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one message; length-delimited values as
    memoryviews, the others as ints (fixed-width ones as raw bytes)."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v, i = bytes(b[i:i + width]), i + width
        else:
            raise ValueError(f"xplane: wire type {kind}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode(errors="replace")


def op_scopes(path: str) -> dict:
    """HLO text of each op on the TPU device planes -> the scope it was
    traced under; ops of the same text in several scopes get them joined
    by ``|``."""
    out = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:
            continue
        parts = list(_fields(plane))
        name = next((_text(v) for g, v in parts if g == 2), "")
        if not name.startswith("/device:TPU:") or "NON_CORE" in name:
            continue
        # stat metadata: the stats' names, and the strings a ``ref`` names
        names = {}
        for g, v in parts:
            if g == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for g, v in parts:
            if g != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            text = next((_text(x) for g2, x in meta if g2 == 2), "")
            for g2, st in meta:
                stat = dict(_fields(st)) if g2 == 5 else {}
                if names.get(stat.get(1)) != "tf_op":
                    continue
                scope = (_text(stat[5]) if 5 in stat
                         else names.get(stat.get(7), "")).rstrip(":")
                if text in out and scope not in out[text].split("|"):
                    scope = f"{out[text]}|{scope}"
                out[text] = scope
    return out


def op_label(text: str) -> str:
    """An op's kind and output shape from its HLO text: ``%copy.250 =
    bf16[16,8,1024,16,128]{...} copy(...)`` -> ``copy bf16[16,8,1024,16,128]``;
    the text itself where it has no such form."""
    m = re.match(r"%([A-Za-z_-]+?)(?:[._]\d+)*\s*=\s*(\(?[a-z0-9]+\[[\d,]*\])?",
                 text)
    if not m:
        return text[:80]
    return f"{m.group(1)} {m.group(2) or ''}".strip()


@dataclass
class Op:
    start: int
    end: int
    module: str
    label: str          # kind and output shape (``op_label``)
    program: int = 0    # id of the program run that holds it
    scope: str = ""     # the scope it was traced under, where the file says


@dataclass
class Reduced:
    """A traced window, reduced."""
    window_ns: tuple
    host_window_s: tuple           # perf_counter at the window's ends
    planes: int
    busy_ns: float
    ops: list = field(default_factory=list)          # Ops of the first plane
    modules: dict = field(default_factory=dict)      # name -> [ns, count]
    programs: dict = field(default_factory=dict)     # id -> [ns, count]
    gaps: list = field(default_factory=list)         # (label, ns)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def module_time(self, pattern: str) -> tuple:
        """(seconds, runs) of the programs whose name matches ``pattern``."""
        ns = n = 0
        for name, (t, c) in self.modules.items():
            if re.search(pattern, name):
                ns += t
                n += c
        return ns / 1e9, n

    def scoped(self, pattern: str) -> list:
        """The ops traced under a scope that matches ``pattern``."""
        return [o for o in self.ops if o.scope and re.search(pattern, o.scope)]

    def program_time(self, ids) -> tuple:
        """(seconds, runs) of the programs with these ids."""
        hits = [self.programs[i] for i in set(ids) if i in self.programs]
        return sum(t for t, _ in hits) / 1e9, sum(c for _, c in hits)

    def ticks(self, ticks: list) -> list:
        """The harness ticks that ran wholly inside the traced window."""
        lo, hi = self.host_window_s
        return [t for t in ticks if lo <= t.t0 and t.t1 <= hi]

    def breakdown(self) -> dict:
        by_op = defaultdict(float)
        for o in self.ops:
            if o.label.split(" ")[0] not in CONTAINERS:
                by_op[f"{o.module}:{o.label}"] += (o.end - o.start) / 1e9
        gaps = defaultdict(float)
        for label, ns in self.gaps:
            gaps[label] += ns / 1e9
        top = lambda d: [[k, v] for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(by_op), "idle_gaps": top(gaps)}


def reduce(path: str, host_window_s: tuple = (0.0, 0.0)) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, host_window_s,
                         op_scopes(path))


def reduce_planes(planes, host_window_s: tuple = (0.0, 0.0),
                  scopes: dict = None) -> Reduced:
    """``planes``: the planes of a ``jax.profiler.ProfileData``, each with
    ``name`` and ``lines``; a line with ``name`` and ``events``; an event
    with ``name``, ``start_ns``, ``duration_ns`` and ``stats``.  ``scopes``:
    an op's HLO text -> its scope (``op_scopes``)."""
    scopes = scopes or {}
    host_events = []                  # (start, end, name)
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in plane.name:
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host_events.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                        ev.name))
    if not devices:
        raise RuntimeError("trace: no TPU device plane")
    windows = [(a, b) for a, b, n in host_events if n == WINDOW]
    if windows:
        w0, w1 = windows[0]
    else:                      # no window span: the whole recorded extent
        w0 = min(e[0] for e in host_events)
        w1 = max(e[1] for e in host_events)
    busy = []
    first_ops, first_modules, first_union = None, None, None
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ())
                      if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)
        ops = []
        j = 0
        for ev in sorted(lines[OPS_LINE].events if OPS_LINE in lines else (),
                         key=lambda e: e.start_ns):
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            if b <= w0 or a >= w1:
                continue
            while j < len(mods) and mods[j][1] < a:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= a else "?"
            ops.append(Op(max(a, w0), min(b, w1), module_name(mod),
                          op_label(ev.name), program_id(mod),
                          scopes.get(ev.name, "")))
        union = _interval_union([(o.start, o.end) for o in ops])
        busy.append(sum(b - a for a, b in union))
        if first_ops is None:
            first_ops, first_union = ops, union
            first_modules = defaultdict(lambda: [0, 0])
            first_programs = defaultdict(lambda: [0, 0])
            for a, b, name in mods:
                for table, key in ((first_modules, module_name(name)),
                                   (first_programs, program_id(name))):
                    table[key][0] += min(b, w1) - max(a, w0)
                    table[key][1] += 1
    red = Reduced(window_ns=(w0, w1), host_window_s=host_window_s,
                  planes=len(devices), busy_ns=sum(busy) / len(busy),
                  ops=first_ops, modules=dict(first_modules),
                  programs=dict(first_programs))
    red.gaps = _name_gaps(first_union, (w0, w1), host_events)
    return red


#: idle gaps shorter than this are summed under one label, not named
SHORT_GAP_NS = 20_000


def _innermost(events: list, points: list) -> list:
    """For each of the sorted ``points``, the name of the shortest event
    of ``events`` (start, end, name) that holds it, or ``-``."""
    events = sorted(events)
    active, out, k = [], [], 0
    for p in points:
        while k < len(events) and events[k][0] <= p:
            active.append(events[k])
            k += 1
        active = [e for e in active if e[1] >= p]
        out.append(min(active, key=lambda e: e[1] - e[0])[2]
                   if active else "-")
    return out


def _name_gaps(union: list, window: tuple, host_events: list) -> list:
    w0, w1 = window
    edges = [w0] + [x for iv in union for x in iv] + [w1]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    short = sum(b - a for a, b in gaps if b - a < SHORT_GAP_NS)
    long_ = [(a, b) for a, b in gaps if b - a >= SHORT_GAP_NS]
    mids = [(a + b) // 2 for a, b in long_]
    spans = _innermost([e for e in host_events if e[2].startswith("bench.")
                        and e[2] != WINDOW], mids)
    inner = _innermost([e for e in host_events
                        if not e[2].startswith("bench.")], mids)
    out = [(f"{s}/{i}", b - a) for s, i, (a, b) in zip(spans, inner, long_)]
    if short:
        out.append((f"gaps under {SHORT_GAP_NS // 1000} us", short))
    return out


class Tracer:
    """Start and stop the JAX profiler between ticks and reduce what it
    recorded.  The trace is written under ``out_dir`` and deleted once
    reduced."""

    def __init__(self, out_dir: Path):
        self.dir = Path(out_dir) / "trace"
        self.host_window = (0.0, 0.0)
        self._span = None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()
        self.host_window = (time.perf_counter(), 0.0)

    def stop(self) -> None:
        if self._span is None:
            return
        self.host_window = (self.host_window[0], time.perf_counter())
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Reduced:
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        try:
            return reduce(paths[0], self.host_window)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
