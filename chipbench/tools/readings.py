"""Read the correctness numbers of one cell on many seeds in one process:
the program's widest logit gap and the fp8 control's, on the same sample.

    python3 chipbench/tools/readings.py --workload <cell> \
        --seeds 1,2,3 --seconds 20

Each seed is a whole run at the cell's own size and load (a shorter window
is enough to finish the mix's longest requests); the limit in
``cells/<cell>.json`` is set from these readings.  Prints one JSON line
per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import harness
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: needs a TPU")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        try:
            out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, t_process=t0,
                                   control=not args.no_control)
            line = {"seed": seed, "correct": out["result"]["correct"],
                    **out["readings"], "notes": out["notes"],
                    "metrics": {k: v["value"] for k, v in
                                out["result"]["metrics"].items()}}
        except Exception as e:  # one seed's failure is a reading too
            line = {"seed": seed, "error": repr(e)[:500]}
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
