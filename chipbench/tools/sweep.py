"""Find an open-loop cell's knee: serve its traffic at a list of fixed rates
on one engine and report, per rate, what was completed and whether the
backlog grew.

    python3 chipbench/tools/sweep.py --workload <cell> \
        --rates 8,9,10,11,12 --seconds 40 [--seed 1]

The queue (requests sent and not yet admitted) is read every second.  A
rate's backlog grows where the queue's least-squares slope over the run
exceeds 2 % of the rate per second.  The knee is the highest rate below the
first whose backlog grew; the capacity is the rate at which the system
completed requests at that first rate, the most it sustains.  The cell's
``rate_per_s`` is set once, at about 0.8 x the capacity, and recorded as a
number in its traffic file.  Prints one JSON line per rate, then the knee
and the capacity.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import harness, stats, traffic as traffic_mod
    from chipbench.loop import Driver
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    cell = harness.load_cell(ROOT, args.workload)
    engine, sched = harness.build(cell, args.seed)
    vocab = cell.config["model"]["vocab_size"]
    harness.warm(sched, cell.traffic, vocab)
    knee, capacity, any_grew = 0.0, None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        t = dataclasses.replace(cell.traffic, rate_per_s=rate)
        driver = Driver(sched, traffic_mod.Requests(t, vocab, args.seed),
                        harness.make_request)
        depth = []
        t0 = time.perf_counter()
        every_second = [(t0 + k, lambda k=k: depth.append(
            (k, len(engine.queue)))) for k in range(1, int(args.seconds))]
        driver.run(t0, t0 + args.seconds, hooks=every_second)
        depth.append((args.seconds, len(engine.queue)))
        slope = float(np.polyfit(*zip(*depth), 1)[0])
        grew = slope > 0.02 * rate
        done = [r for r in driver.recs if r.finished is not None]
        if grew and not any_grew:
            capacity = len(done) / args.seconds
        any_grew = any_grew or grew
        if not any_grew:
            knee = rate
        t1 = t0 + args.seconds
        half = t0 + args.seconds / 2
        ttft = stats.ttfts(driver.recs, half, t1)
        print(json.dumps({
            "rate": rate, "arrived": len(driver.recs),
            "finished_per_s": len(done) / args.seconds,
            "tokens_per_s": stats.tokens_in(driver.recs, half, t1)
            / (t1 - half),
            "queue_every_second": [q for _, q in depth],
            "queue_slope_per_s": slope, "backlog_grew": grew,
            "ttft_p50_ms": 1e3 * (stats.percentile(ttft, 50) or 0),
            "ttft_p95_ms": 1e3 * (stats.percentile(ttft, 95) or 0),
            "itl_p95_ms": 1e3 * (stats.percentile(
                stats.token_gaps(driver.recs, half, t1), 95) or 0),
            "preemptions": sched.preemptions}), flush=True)
        engine.queue.clear()         # the backlog is abandoned, not served
        while engine.active:
            sched.tick()
        engine.finished.clear()
    print(json.dumps({"knee": knee, "capacity": capacity,
                      "rate_at_0.8": capacity and round(0.8 * capacity, 2)}))


if __name__ == "__main__":
    main()
