"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, their metrics and their files are listed in ``BENCHMARK.json``
at the checkout's root.  The run builds the cell's engine, warms every
shape its traffic uses, pre-rolls that traffic, measures ``--seconds`` of
wall time, checks the served tokens against the configuration's plain
reference, and prints the numbers compared beside their limits as the last
lines of standard error and one JSON object as the last line of standard
output.  With ``--trace 1`` the metrics are the cell's per-layer ones, read
from a profiler trace of the window's last seconds and the client's stamps.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's compile cache is kept in ``.jax_cache`` at
the checkout's root.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"chipbench: {args.workload} needs {cell.chips} TPU "
                         f"chip(s); JAX found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_process=T_PROCESS)
    result = out["result"]
    print("notes " + json.dumps(out["notes"]))
    print("readings " + json.dumps(out["readings"]))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
