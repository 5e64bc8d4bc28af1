"""Drive ``Scheduler.submit`` / ``Scheduler.tick`` from the client side on
the wall clock.

Arrivals due by now are submitted before each tick; the tick's tokens are
stamped with the host time at which it returned (the engine drains every
token to the host inside the step).  When nothing is queued or running the
client sleeps until the next arrival.  Each phase of the loop runs under
a ``jax.profiler.TraceAnnotation`` named ``bench.<phase>``, so the idle
gaps of a traced window can be named by what the host was doing.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

from chipbench.stats import Rec


@dataclass
class Tick:
    t0: float
    t1: float
    #: requests first admitted in this tick, and their prompt tokens
    admitted: int = 0
    prefill_tokens: int = 0
    #: prompt length of each prefill this tick
    prefill_lens: list = field(default_factory=list)
    #: rows that decoded a token in this tick, and each row's KV length
    decode_kv: list = field(default_factory=list)


class Driver:
    """One run's traffic against one scheduler.

    ``requests`` is a ``traffic.Requests``; ``make_request(i, prompt,
    max_new)`` builds the engine's request object.
    """

    def __init__(self, sched, requests, make_request: Callable,
                 clock: Callable[[], float] = time.perf_counter):
        self.sched = sched
        self.engine = sched.engine
        self.requests = requests
        self.make_request = make_request
        self.clock = clock
        self.recs: list[Rec] = []
        self.ticks: list[Tick] = []
        self._live: dict = {}          # rid -> (request, rec, seen tokens)
        self._next = 0                 # next request index to send
        self._due: list = []           # closed loop: arrival times pending

    # -- sending ---------------------------------------------------------------

    def _send(self, arrival: float) -> None:
        i = self._next
        self._next += 1
        plen, out = self.requests.size(i)
        req = self.make_request(i, self.requests.prompt(i), out)
        rec = Rec(index=i, arrival=arrival, prompt_len=plen, max_new=out)
        self.recs.append(rec)
        if self.sched.submit(req):
            self._live[req.request_id] = [req, rec, 0]
        else:
            rec.rejected = True
            if self.requests.traffic.loop == "closed":
                self._due.append(self.clock()
                                 + self.requests.traffic.think_s)

    # -- stamping --------------------------------------------------------------

    def _stamp(self, tick: Tick) -> None:
        done = []
        for rid, entry in self._live.items():
            req, rec, seen = entry
            n = len(req.output_tokens)
            if n == seen:
                continue
            if n < seen:                       # preempted: its stream restarts
                rec.restarts += 1
                entry[2] = n
                continue
            fresh = n - seen
            rec.stamps.extend([tick.t1] * fresh)
            if seen == 0:
                # a prefill in this tick gave the first token of the stream
                if rec.admitted is None:
                    rec.admitted = tick.t0
                if req.slot >= 0:
                    rec.slot = req.slot
                tick.admitted += 1
                tick.prefill_tokens += rec.prompt_len
                tick.prefill_lens.append(rec.prompt_len)
                fresh -= 1
            if fresh:
                tick.decode_kv.append(req.index)
            entry[2] = n
            if req.state == "finished":
                rec.finished = tick.t1
                done.append(rid)
        for rid in done:
            del self._live[rid]
            if self.requests.traffic.loop == "closed":
                self._due.append(tick.t1 + self.requests.traffic.think_s)

    # -- the loop --------------------------------------------------------------

    def run(self, t_start: float, t_end: float, *,
            hooks: Optional[list] = None) -> None:
        """Serve from ``t_start`` until the first tick boundary at or after
        ``t_end``.  ``hooks``: (time, fn) pairs, each fn called once between
        ticks when the clock passes its time."""
        traffic = self.requests.traffic
        hooks = sorted(hooks or [], key=lambda h: h[0])
        if traffic.loop == "open":
            arrivals = list(t_start + self.requests.arrival_offsets(
                t_end - t_start))
            arrivals.reverse()
        else:
            arrivals = []
            self._due = [t_start] * traffic.clients
        engine = self.engine
        while True:
            now = self.clock()
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1]()
                now = self.clock()
            if now >= t_end:
                break
            with TraceAnnotation("bench.submit"):
                while arrivals and arrivals[-1] <= now:
                    self._send(arrivals.pop())
                if self._due:
                    ready = sorted(t for t in self._due if t <= now)
                    self._due = [t for t in self._due if t > now]
                    for t in ready:
                        self._send(t)
            if engine.queue or engine.active:
                t0 = self.clock()
                with TraceAnnotation("bench.tick"):
                    self.sched.tick()
                tick = Tick(t0, self.clock())
                with TraceAnnotation("bench.stamp"):
                    self._stamp(tick)
                self.ticks.append(tick)
                continue
            upcoming = [t_end]
            if arrivals:
                upcoming.append(arrivals[-1])
            if self._due:
                upcoming.append(min(self._due))
            if hooks:
                upcoming.append(hooks[0][0])
            with TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(upcoming) - self.clock()))
