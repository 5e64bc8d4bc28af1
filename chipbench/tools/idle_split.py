"""Where the device's idle time goes, by what the program was doing: one
traced run of a cell, made as ``run.py`` makes it, and then

- the traced window's device idle time by the innermost program span the
  host was in (``spans.idle_split``, the spans put on the trace's clock
  through the two ends of ``bench.window``);
- each idle gap of 20 us or more named by its innermost program span and
  the innermost runtime event of the trace's host plane that hold its
  midpoint (as the harness's ``breakdown`` names them by ``bench.*`` span);
- the mean host time per decode-only tick of each span below it, on the
  untraced stretch of the window.

    python3 chipbench/tools/idle_split.py --workload <cell> --seed <n> \
        --seconds 45 --out <file>.json

Prints ``run.py``'s lines, and writes the three tables to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
#: the spans the program records (``repro.obs.wall``)
PROGRAM = ("sched.tick", "engine.", "account", "bridge.")


def _innermost(events: list, points: list) -> list:
    """For each sorted point, the shortest of ``events`` (start, end, name)
    holding it, or ``-``."""
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default="45")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, run, spans, trace_reduce

    kept = {}
    reduce_planes, reduce_trace = (trace_reduce.reduce_planes,
                                   trace_reduce.Tracer.reduce)

    def keep_host(planes, *a, **k):
        planes = list(planes)
        kept["host"] = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for p in planes if p.name.startswith("/host:")
                        for line in p.lines for ev in line.events]
        return reduce_planes(planes, *a, **k)

    def keep_reduced(self):
        kept["trace"] = reduce_trace(self)
        return kept["trace"]

    trace_reduce.reduce_planes = keep_host
    trace_reduce.Tracer.reduce = keep_reduced
    run.main(["--workload", args.workload, "--seed", args.seed,
              "--seconds", args.seconds, "--trace", "1"])

    trace = kept["trace"]
    view = SimpleNamespace(trace=trace, window=None)
    traced = spans.window(view, traced=True)
    out = {"workload": args.workload, "seed": int(args.seed),
           "idle_s": sum(b - a for a, b in spans.idle(trace)) / 1e9,
           "window_s": trace.window_s}
    if traced is None:
        out["error"] = "the program recorded no spans"
    else:
        out["idle_by_span_s"] = dict(sorted(
            ((k, v / 1e9) for k, v in
             spans.idle_split(trace, traced).items()), key=lambda kv: -kv[1]))
        gaps = [(a, b) for a, b in spans.idle(trace) if b - a >= 20_000]
        mids = [(a + b) // 2 for a, b in gaps]
        host = kept["host"]
        program = _innermost([e for e in host
                              if e[2].startswith(PROGRAM)], mids)
        runtime = _innermost([e for e in host if not e[2].startswith(PROGRAM)
                              and not e[2].startswith("bench.")], mids)
        named = defaultdict(float)
        for p, r, (a, b) in zip(program, runtime, gaps):
            named[f"{p} / {r}"] += (b - a) / 1e9
        out["idle_gaps_s"] = dict(sorted(named.items(),
                                         key=lambda kv: -kv[1])[:40])
    # the untraced stretch (the window opened its length less the traced
    # seconds before the trace started): mean host ms per decode-only tick
    # of each span name below it, the outermost of each name
    view.window = SimpleNamespace(t_open=trace.host_window_s[0] - (
        float(args.seconds) - harness.TRACE_SECONDS))
    recs = spans.window(view)
    if recs is not None:
        tree = spans.Tree(recs)
        ticks = spans.decode_ticks(tree)
        names = sorted({r.name for r in recs} - {spans.TICK})
        out["decode_ticks"] = len(ticks)
        if ticks:
            out["tick_ms"] = sum(t.end_ns - t.start_ns for t in ticks) / (
                1e6 * len(ticks))
            out["per_tick_ms"] = {n: sum(tree.time_in(t, {n}) for t in ticks)
                                  / (1e6 * len(ticks)) for n in names}
            out["per_tick_count"] = {n: sum(tree.count(t, {n}) for t in ticks)
                                     / len(ticks) for n in names}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
