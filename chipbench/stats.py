"""Arithmetic of the client-side metrics: percentiles, and what a window of
stamped requests delivered.

A request's clock starts at its scheduled arrival.  Each of its tokens
carries the host time at which the tick that produced it returned.  The
window is ``[t_open, t_close]``: a token counts in it by its stamp, a
request by its arrival.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between closest ranks; None
    for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass
class Rec:
    """One request as the client sees it."""
    index: int
    arrival: float
    prompt_len: int
    max_new: int
    #: host time of each token delivered, in order
    stamps: list = field(default_factory=list)
    #: start of the tick that first admitted it
    admitted: Optional[float] = None
    finished: Optional[float] = None
    rejected: bool = False
    restarts: int = 0
    #: the engine slot that served it
    slot: int = -1


def arrived_in(recs, t_open: float, t_close: float) -> list:
    return [r for r in recs if t_open <= r.arrival < t_close]


def ttfts(recs, t_open: float, t_close: float) -> list:
    """Seconds from arrival to first token of the requests arriving in the
    window; one still waiting at the close counts the time waited so far."""
    out = []
    for r in arrived_in(recs, t_open, t_close):
        first = r.stamps[0] if r.stamps else None
        out.append((first if first is not None and first <= t_close
                    else t_close) - r.arrival)
    return out


def queue_waits(recs, t_open: float, t_close: float) -> list:
    """Seconds from arrival to the start of the admitting tick, same
    population and censoring as ``ttfts``."""
    out = []
    for r in arrived_in(recs, t_open, t_close):
        t = r.admitted if r.admitted is not None and r.admitted <= t_close \
            else t_close
        out.append(max(0.0, t - r.arrival))
    return out


def tokens_in(recs, t_open: float, t_close: float) -> int:
    return sum(1 for r in recs for s in r.stamps if t_open <= s <= t_close)


def token_gaps(recs, t_open: float, t_close: float) -> list:
    """Every gap between consecutive tokens of a request whose later token
    lies in the window."""
    out = []
    for r in recs:
        s = r.stamps
        out.extend(s[j] - s[j - 1] for j in range(1, len(s))
                   if t_open <= s[j] <= t_close)
    return out
