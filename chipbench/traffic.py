"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and makes its requests from the run's seed.

Every seed gets the same set of sizes and arrival gaps, in another order, so
that two seeds load the system alike and differ only in the token ids and in
which request comes when:

- sizes: ``pool`` prompt lengths and as many output lengths, taken at evenly
  spaced quantiles of their law (``lognormal`` with ``median`` and
  ``sigma``, clipped to ``min``/``max``; prompt lengths snapped to the
  nearest rung of ``ladder``), each list shuffled by the seed;
- open-loop arrivals (``loop: open``): ``pool`` gaps drawn once from the
  law (``poisson``, or ``gamma`` of ``shape``) with the mix's fixed
  ``pattern_seed``, scaled to a mean of exactly ``1 / rate_per_s``, then
  rotated by an offset drawn from the seed.  Requests and gaps repeat with
  the pool, so with ``pool`` = ``rate_per_s`` x the run's seconds every
  window of that length holds each size and each gap once, whatever the
  seed;
- closed loop (``loop: closed``): ``clients`` clients, each sending its
  next request ``think_s`` after its last one finished.

A request's prompt holds token ids drawn uniformly from [1, vocab) by a
generator seeded from (seed, request index).
"""

from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from pathlib import Path

import numpy as np

LAWS = ("lognormal",)
ARRIVALS = ("poisson", "gamma")


@dataclass(frozen=True)
class Traffic:
    name: str
    loop: str
    max_batch: int
    prompt: dict
    output: dict
    pool: int
    preroll_s: float
    clients: int = 0
    think_s: float = 0.0
    rate_per_s: float = 0.0
    arrival: dict = None
    pattern_seed: int = 0

    @property
    def ladder(self) -> tuple:
        return tuple(self.prompt["ladder"])


def load(path: Path) -> Traffic:
    spec = json.loads(Path(path).read_text())
    t = Traffic(name=Path(path).stem, **{k: v for k, v in spec.items()
                                          if not k.startswith("_")})
    if t.loop not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed, not {t.loop!r}")
    if t.loop == "closed" and t.clients < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if t.loop == "open":
        if t.rate_per_s <= 0 or not t.arrival or t.arrival["law"] not in ARRIVALS:
            raise ValueError(f"{path}: an open loop needs rate_per_s > 0 and an "
                             f"arrival law of {ARRIVALS}")
    for part in (t.prompt, t.output):
        if part["law"] not in LAWS:
            raise ValueError(f"{path}: length law must be one of {LAWS}")
    return t


def _quantile_lengths(law: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the law."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    if "ladder" in law:
        rungs = np.asarray(law["ladder"], np.float64)
        return rungs[np.abs(np.log(x)[:, None] - np.log(rungs)[None]).argmin(1)
                     ].astype(np.int64)
    return np.clip(np.rint(x), law.get("min", 1),
                   law.get("max", np.inf)).astype(np.int64)


def _gaps(t: Traffic) -> np.ndarray:
    rng = np.random.default_rng(t.pattern_seed)
    if t.arrival["law"] == "poisson":
        g = rng.exponential(1.0, t.pool)
    else:
        k = float(t.arrival["shape"])
        g = rng.gamma(k, 1.0 / k, t.pool)
    return g / g.mean() / t.rate_per_s


def seed_rng(seed: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *more])


class Requests:
    """The requests of one run, in the order they are sent."""

    def __init__(self, traffic: Traffic, vocab: int, seed: int):
        self.traffic = traffic
        self.vocab = vocab
        self.seed = int(seed)
        rng = seed_rng(seed, 1)
        n = traffic.pool
        self.prompt_lens = rng.permutation(_quantile_lengths(traffic.prompt, n))
        self.output_lens = rng.permutation(_quantile_lengths(traffic.output, n))
        if traffic.loop == "open":
            self.gaps = np.roll(_gaps(traffic), -int(rng.integers(n)))
        else:
            self.gaps = None

    def size(self, i: int) -> tuple[int, int]:
        """(prompt length, output length) of the ``i``-th request."""
        j = i % self.traffic.pool
        return int(self.prompt_lens[j]), int(self.output_lens[j])

    def prompt(self, i: int) -> list:
        n, _ = self.size(i)
        return seed_rng(self.seed, 2, i).integers(1, self.vocab, n).tolist()

    def arrival_offsets(self, horizon_s: float) -> np.ndarray:
        """Open loop: arrival times, in seconds after the start, up to
        ``horizon_s``."""
        reps = int(math.ceil(horizon_s * self.traffic.rate_per_s
                             / self.traffic.pool)) + 1
        t = np.cumsum(np.tile(self.gaps, reps))
        return t[t <= horizon_s]
