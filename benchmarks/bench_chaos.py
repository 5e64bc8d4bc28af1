"""Chaos benchmark: goodput / TTFT p99 / MTTR vs fault rate + ladder ablation.

Seeded transient bridge faults (DESIGN.md §11) swept over a two-replica
cluster serving a shared-prefix workload in waves (the waves warm the
offload host store, so later waves restore over the faulted channel — the
restore-corruption class is live, not just MAC rejects on decode traffic).

Two tables, one module:

1. **Fault-rate sweep.**  ``FaultPlan.transient(rate)`` at each swept rate:
   goodput (tokens per virtual second of makespan), TTFT p99, and MTTR
   (mean recovery seconds per injected fault event).  The chaos invariant
   is asserted inline at every point: token streams byte-identical to the
   fault-free run, zero requests lost — faults only move the clock.

2. **Ladder ablation.**  At the highest swept rate, the degradation ladder
   on vs off (``DegradationLadder(enabled=False)`` records escalation
   requests but pins level 0).  The ablation plan carries the two fault
   classes the ladder's rungs actually answer (MAC rejects + restore
   corruption; teardown is channel-level and rung-independent).  Ladder-on
   must strictly beat ladder-off goodput — the rungs pay for themselves:
   sync restore re-sends one block instead of the whole MAC'd prefix, and
   bypassed crossings retry alone instead of re-paying a fused flush whose
   reject probability is amplified across every constituent ciphertext.

Everything runs on the virtual clock: bit-deterministic, checked into
``BENCH_chaos.json`` (CI drift gate: ``python -m benchmarks.bench_chaos
--check``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from repro.cluster import ReplicaConfig, build_cluster
from repro.configs.base import all_configs, smoke_config
from repro.models.model import Model
from repro.resilience import DegradationLadder, FaultPlan
from repro.serving.engine import Request
from repro.serving.sampler import SamplingParams

#: swept per-crossing transient fault rates (0 = the identity baseline)
RATES = (0.0, 0.05, 0.15, 0.3)
SEED = 11
N_REPLICAS = 2
WAVES = 3
WAVE_SIZE = 6
MAX_NEW_TOKENS = 6
#: shared prefix: 4 full blocks at block_tokens=8 — the warm-restore unit
PREFIX = list(range(1, 33))

REL_TOL = 1e-9
DRIFT_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_chaos.json")

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = Model(smoke_config(all_configs()["olmo-1b"]))
    return _MODEL


def _cfg() -> ReplicaConfig:
    # coalescing ON so the fused-ciphertext fault semantics (and the
    # bypass rung) are live; uniform output lengths keep the last rung's
    # full-width step shape-neutral (ready set == max_batch)
    return ReplicaConfig(max_batch=2, max_len=64,
                         coalesce_small_crossings=True)


def _run_cluster(plan, *, ladder_enabled: bool = True) -> dict:
    """One cluster serving run in warm-up waves; returns tokens + metrics."""
    cluster = build_cluster(_model(), n_replicas=N_REPLICAS,
                            fault_plan=plan, replica_cfg=_cfg(), seed=0)
    if not ladder_enabled:
        for r in cluster.replicas:
            if r.faults is not None:
                r.faults.ladder = DegradationLadder(enabled=False)
    submitted = 0
    for wave in range(WAVES):
        for i in range(WAVE_SIZE):
            rid = f"w{wave}r{i}"
            ok = cluster.submit(Request(
                rid, prompt=PREFIX + [100 + wave * WAVE_SIZE + i] * 8,
                sampling=SamplingParams(max_new_tokens=MAX_NEW_TOKENS)))
            assert ok is not None, f"cluster shed {rid} — workload too big"
            submitted += 1
        # drain the wave: finished requests evict through the reuse-aware
        # offload path, so the next wave's shared prefix restores warm
        cluster.run()
    stats = cluster.stats()
    tokens = {e["request"].request_id: tuple(e["request"].output_tokens)
              for e in cluster.request_log}
    ttfts = [t["ttft_s"] for t in cluster.ttfts()]
    faults = [r["faults"] for r in stats["replicas"]
              if r["faults"] is not None]
    injected = sum(f["injected_events"] for f in faults)
    recovery = sum(f["recovery_s"] for f in faults)
    ladders = [r.faults.ladder for r in cluster.replicas
               if r.faults is not None]
    out = {
        "submitted": submitted,
        "finished": stats["finished"],
        "lost": submitted - stats["finished"],
        "total_tokens": stats["total_tokens"],
        "makespan_s": stats["makespan_s"],
        "goodput_tok_s": (stats["total_tokens"] / stats["makespan_s"]
                          if stats["makespan_s"] > 0 else 0.0),
        "ttft_p99_ms": float(np.percentile(ttfts, 99)) * 1e3,
        "injected_events": injected,
        "mttr_ms": (recovery / injected * 1e3) if injected else 0.0,
        "warm_blocks_restored": stats["warm_blocks_restored"],
        "escalations": sum(l.escalations_requested for l in ladders),
        "max_rung": max((max((t.level for t in l.transitions), default=0)
                         for l in ladders), default=0),
        "tokens": tokens,
    }
    cluster.close()
    return out


def fault_rate_sweep() -> list[dict]:
    """Goodput/TTFT-p99/MTTR at each swept transient-fault rate, with the
    chaos invariant (byte-identical tokens, zero lost) asserted inline."""
    rows = []
    baseline_tokens = None
    for rate in RATES:
        plan = FaultPlan.transient(seed=SEED, rate=rate) if rate else None
        r = _run_cluster(plan)
        if r["lost"]:
            raise AssertionError(
                f"{r['lost']} requests lost at fault rate {rate}")
        if baseline_tokens is None:
            baseline_tokens = r["tokens"]
        elif r["tokens"] != baseline_tokens:
            raise AssertionError(
                f"token streams diverged from fault-free run at rate {rate}"
                " — faults moved data, not just the clock")
        row = {k: v for k, v in r.items() if k != "tokens"}
        row["rate"] = rate
        rows.append(row)
    return rows


def ladder_ablation() -> dict:
    """Ladder on vs off at the highest swept rate (rung-relevant fault mix:
    MAC rejects + restore corruption; teardown is rung-independent)."""
    rate = RATES[-1]
    plan = FaultPlan(seed=SEED, crossing_failure_p=rate,
                     restore_corruption_p=rate)
    on = _run_cluster(plan, ladder_enabled=True)
    off = _run_cluster(plan, ladder_enabled=False)
    if on["tokens"] != off["tokens"]:
        raise AssertionError("ladder changed token streams — it may only "
                             "change execution shape, never data")
    if on["lost"] or off["lost"]:
        raise AssertionError("requests lost in the ablation arms")
    return {
        "rate": rate,
        "ladder_on_goodput_tok_s": on["goodput_tok_s"],
        "ladder_off_goodput_tok_s": off["goodput_tok_s"],
        "goodput_ratio": on["goodput_tok_s"] / off["goodput_tok_s"],
        "ladder_on_makespan_s": on["makespan_s"],
        "ladder_off_makespan_s": off["makespan_s"],
        "ladder_on_mttr_ms": on["mttr_ms"],
        "ladder_off_mttr_ms": off["mttr_ms"],
        "escalations_on": on["escalations"],
        "escalations_requested_off": off["escalations"],
        "max_rung_on": on["max_rung"],
    }


def payload() -> dict:
    return {"sweep": fault_rate_sweep(), "ablation": ladder_ablation()}


def run() -> list[str]:
    data = payload()
    lines = []
    for r in data["sweep"]:
        lines.append(
            f"chaos/goodput_rate{r['rate']:g},{r['goodput_tok_s']:.2f},"
            f"tok/s at transient fault rate {r['rate']:g} "
            f"({r['injected_events']} injected events, 0 lost, "
            f"tokens byte-identical to fault-free)")
        lines.append(
            f"chaos/ttft_p99_rate{r['rate']:g},{r['ttft_p99_ms']:.3f},"
            f"TTFT p99 (ms) at rate {r['rate']:g}")
        lines.append(
            f"chaos/mttr_rate{r['rate']:g},{r['mttr_ms']:.4f},"
            f"mean recovery ms per injected fault at rate {r['rate']:g}")
    ab = data["ablation"]
    lines.append(
        f"chaos/ladder_goodput_ratio,{ab['goodput_ratio']:.6f},"
        f"ladder-on/off goodput at rate {ab['rate']:g} "
        f"(on {ab['ladder_on_goodput_tok_s']:.2f} vs off "
        f"{ab['ladder_off_goodput_tok_s']:.2f} tok/s, "
        f"max rung {ab['max_rung_on']})")
    if ab["goodput_ratio"] <= 1.0:
        raise AssertionError(
            f"degradation ladder did not pay for itself at rate "
            f"{ab['rate']}: on/off goodput ratio {ab['goodput_ratio']:.6f}")
    # the sweep's monotone cost story: the faulted points are all slower
    # than fault-free (recovery is charged, never hidden)
    base = data["sweep"][0]["goodput_tok_s"]
    degraded = all(r["goodput_tok_s"] < base for r in data["sweep"][1:])
    lines.append(
        f"chaos/faults_cost_goodput,{float(degraded):.1f},"
        f"every faulted rate's goodput < fault-free baseline {base:.2f}")
    return lines


# ---------------------------------------------------------------------------------
# BENCH_chaos.json drift gate
# ---------------------------------------------------------------------------------


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(abs(a), abs(b), 1e-30)


def _diff(kind: str, gold: dict, fresh: dict, problems: list) -> None:
    for key, val in fresh.items():
        gv = gold.get(key)
        ok = (_close(val, gv) if isinstance(val, float)
              and isinstance(gv, (int, float)) else val == gv)
        if not ok:
            problems.append(f"{kind} {key}: {gv!r} -> {val!r}")


def check_drift(path: str) -> list[str]:
    with open(path) as f:
        golden = json.load(f)
    fresh = payload()
    problems: list[str] = []
    gold_sweep = golden.get("sweep", [])
    if len(gold_sweep) != len(fresh["sweep"]):
        problems.append(
            f"sweep row count {len(gold_sweep)} -> {len(fresh['sweep'])}")
    else:
        for g, f_ in zip(gold_sweep, fresh["sweep"]):
            _diff(f"sweep rate={f_['rate']:g}", g, f_, problems)
    _diff("ablation", golden.get("ablation", {}), fresh["ablation"],
          problems)
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
            print("BENCH_chaos.json is stale — regenerate with "
                  "`python -m benchmarks.bench_chaos --write` and review:")
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
