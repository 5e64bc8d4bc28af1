"""The program's MoE assignment counts, read from its wall-clock spans.

After each prompt program and each packed decode step of a model with
experts, the engine records an ``engine.routed`` span whose ``counts`` attr
holds the program's assignments to each (MoE layer, expert held here), an
int array of that shape: below ``engine.consume`` for a decode step, below
``engine.prefill`` for a prompt.  A decode step counts every row it
executed, the bucket's pad rows (copies of the first row) among them.
Where the program records no such span there is nothing to read.
"""

from __future__ import annotations

import numpy as np

ROUTED = "engine.routed"
STEP = "engine.consume"


def counts(rec) -> np.ndarray:
    return np.asarray(rec.attrs["counts"])


def decode_steps(recs: list) -> list:
    """The counts of every packed decode step among ``recs``."""
    steps = {r.id for r in recs if r.name == STEP}
    return [counts(r) for r in recs if r.name == ROUTED and r.parent in steps]


def in_ticks(recs: list, ticks: list) -> list:
    """The counts recorded inside the harness ticks ``ticks`` (prompts and
    decode steps alike)."""
    bounds = [(int(t.t0 * 1e9), int(t.t1 * 1e9)) for t in ticks]
    return [counts(r) for r in recs if r.name == ROUTED
            and any(a <= r.start_ns <= b for a, b in bounds)]
