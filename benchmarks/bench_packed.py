"""Packed ragged decode benchmark: the peak-throughput roofline table.

The unified step-pricing roofline (``ComputeModel`` — the same object the
engine charges and the simulator's ``forward_ms`` derives from; DESIGN.md
§10) priced across model configs x batch x input/output lengths, CC-on
B300.  Decode is weight-read memory-bound at serving batch sizes, so tok/s
climbs near-linearly with batch until the KV read stream takes over at long
contexts — the table makes that crossover visible per config.  Pure
virtual-clock arithmetic: bit-deterministic, checked into
``BENCH_packed.json`` (CI drift gate: ``python -m benchmarks.bench_packed
--check``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from repro.configs.base import get_config
from repro.core.bridge import B300, BridgeModel
from repro.core.compute import ComputeModel

#: the roofline table axes: >=3 configs x batch 8..512 x (input, output)
CONFIGS = ("qwen1p5-4b", "deepseek-moe-16b", "qwen3p6-27b")
BATCHES = (8, 32, 128, 512)
#: (input_len, output_len) pairs; the priced KV depth is the mean decode
#: context, input + output/2
LENGTH_PAIRS = ((128, 128), (1024, 512), (4096, 1024))

#: relative tolerance for the BENCH_packed.json drift check (virtual-clock
#: quantities are deterministic; this absorbs only float round-tripping)
REL_TOL = 1e-9

DRIFT_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_packed.json")


def _config(name: str):
    # ARCH_IDS spells qwen1.5-4b with a dot; keep the benchmark axis
    # filesystem/JSON-friendly
    return get_config({"qwen1p5-4b": "qwen1.5-4b"}.get(name, name))


def roofline_table() -> list[dict]:
    """Peak decode throughput per (config, batch, lengths) off the unified
    roofline — the one pricing source the engine charges per step and
    ``core.simulator.fit_workload`` calibrates against."""
    bridge = BridgeModel(B300, cc_on=True)
    rows = []
    for name in CONFIGS:
        cfg = _config(name)
        cm = ComputeModel(cfg, bridge)
        for batch in BATCHES:
            for in_len, out_len in LENGTH_PAIRS:
                kv = float(in_len + out_len / 2)
                charge = cm.decode_charge([kv] * batch)
                step_s = charge.seconds
                rows.append({
                    "config": name,
                    "batch": batch,
                    "input_len": in_len,
                    "output_len": out_len,
                    "kv_len": kv,
                    "step_ms": step_s * 1e3,
                    "tok_s": batch / step_s,
                    "bound": charge.bound,
                })
    return rows


def payload() -> dict:
    """The deterministic drift payload: the roofline table, virtual-clock
    only."""
    return {"roofline": roofline_table()}


def run() -> list[str]:
    data = payload()
    lines = []
    for r in data["roofline"]:
        # one row per table cell: peak tok/s at that operating point
        lines.append(
            f"packed/roofline_{r['config']}_b{r['batch']}"
            f"_i{r['input_len']}_o{r['output_len']},{r['tok_s']:.1f},"
            f"tok/s at kv={r['kv_len']:g} ({r['bound']}-bound, "
            f"step {r['step_ms']:.3f} ms; unified ComputeModel roofline)")
    return lines


# ---------------------------------------------------------------------------------
# BENCH_packed.json drift gate
# ---------------------------------------------------------------------------------


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(abs(a), abs(b), 1e-30)


def _diff_rows(kind: str, gold: list, fresh: list, keyfields: tuple,
               problems: list) -> None:
    if len(gold) != len(fresh):
        problems.append(f"{kind} row count {len(gold)} -> {len(fresh)}")
        return
    for g, f_ in zip(gold, fresh):
        label = "/".join(str(f_[k]) for k in keyfields)
        for key, val in f_.items():
            gv = g.get(key)
            ok = (_close(val, gv) if isinstance(val, float) else val == gv)
            if not ok:
                problems.append(f"{kind} {label} {key}: {gv!r} -> {val!r}")


def check_drift(path: str) -> list[str]:
    """Recompute the deterministic payload and diff it against `path`."""
    with open(path) as f:
        golden = json.load(f)
    fresh = payload()
    problems: list[str] = []
    _diff_rows("roofline", golden.get("roofline", []), fresh["roofline"],
               ("config", "batch", "input_len", "output_len"), problems)
    return problems


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--write", metavar="PATH", nargs="?",
                    const=DRIFT_PATH, default=None,
                    help="write the deterministic payload as JSON")
    ap.add_argument("--check", metavar="PATH", nargs="?",
                    const=DRIFT_PATH, default=None,
                    help="verify PATH against a fresh recomputation")
    args = ap.parse_args()
    if args.check:
        problems = check_drift(args.check)
        if problems:
            print("BENCH_packed.json is stale — regenerate with "
                  "`python -m benchmarks.bench_packed --write` and review:")
            for p in problems:
                print(f"  {p}")
            sys.exit(1)
        print(f"{os.path.basename(args.check)}: OK")
        return
    if args.write:
        with open(args.write, "w") as f:
            json.dump(payload(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.write}")
        return
    print("\n".join(run()))


if __name__ == "__main__":
    main()
