# One function per paper table. Print ``name,us_per_call,derived`` CSV and,
# with --json, a machine-readable summary (for the BENCH_*.json trajectory):
# per-row values, per-module status AND wall time, so trajectories can track
# both results and cost across PRs.
import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write a machine-readable JSON summary")
    ap.add_argument("--only", metavar="NAME", default=None,
                    help="run only modules whose title contains NAME")
    args = ap.parse_args()

    from . import (bench_bridge, bench_serving, bench_loader, bench_offload,
                   bench_fabric, bench_roofline, bench_cluster, bench_replay,
                   bench_bridge_opt, bench_obs, bench_packed, bench_chaos,
                   bench_tp, bench_quant)
    modules = [
        ("bridge (SS4.1-4.3)", bench_bridge),
        ("serving (SS5.1-5.5)", bench_serving),
        ("loader (SS6.1)", bench_loader),
        ("offload (SS6.2)", bench_offload),
        ("fabric (SS7)", bench_fabric),
        ("roofline (SSRoofline)", bench_roofline),
        ("cluster (SS7 x SS4 L4)", bench_cluster),
        ("replay (SS5.2 bridge-tape counterfactuals)", bench_replay),
        ("bridge_opt (SS5.2 x SS8 arena+coalescer+pipelined-restore)",
         bench_bridge_opt),
        ("obs (SS9 spans+stall-attribution+overhead)", bench_obs),
        ("packed (SS10 ragged decode roofline)",
         bench_packed),
        ("chaos (SS11 fault injection + recovery ladder)", bench_chaos),
        ("tp (SS12 fabric-P2P tensor parallelism + fallback repricing)",
         bench_tp),
        ("quant (SS13 quantized bridge crossings + un-quantize replay)",
         bench_quant),
    ]
    if args.only:
        modules = [(t, m) for t, m in modules if args.only in t]

    print("name,us_per_call,derived")
    failures = 0
    rows = []
    module_status = {}
    module_wall_s = {}
    for title, mod in modules:
        print(f"# --- {title} ---")
        t0 = time.perf_counter()
        try:
            for line in mod.run():
                print(line)
                name, _, rest = line.partition(",")
                value, _, derived = rest.partition(",")
                try:
                    rows.append({"name": name, "value": float(value),
                                 "derived": derived})
                except ValueError:
                    rows.append({"name": name, "value": None, "derived": rest})
            module_status[title] = "ok"
        except Exception:
            failures += 1
            module_status[title] = "error"
            traceback.print_exc()
        wall = time.perf_counter() - t0
        module_wall_s[title] = wall
        slug = title.split(" ", 1)[0]
        rows.append({"name": f"meta/{slug}_wall_s", "value": wall,
                     "derived": f"module wall time ({module_status[title]})"})
        print(f"# {title}: {wall:.2f}s")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "modules": module_status,
                       "module_wall_s": module_wall_s,
                       "failures": failures}, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")

    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
