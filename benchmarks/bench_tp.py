"""Tensor-parallel ladder benchmark: TP 1/2/4/8 with bridge-vs-P2P attribution.

Three payloads, one module (DESIGN.md §12):

1. **The TP ladder, modeled.**  nemotron-4-340b on CC-on B300, TP degree
   1/2/4/8: per-device decode step off the unified roofline (FLOPs and HBM
   divide by the degree) plus the ring allreduce over the tenant fabric
   (``2 (tp-1)/tp`` x activations at ``fabric_p2p_bw``).  The ladder is the
   tentpole's economic claim made checkable: the allreduce rides the one
   path CC does not serialize, so sharding a 340B model across a tenant's
   partition buys near-linear step speedup instead of drowning in bridge
   tolls.  Pure virtual-clock arithmetic, checked into ``BENCH_tp.json``
   (CI drift gate: ``python -m benchmarks.bench_tp --check``).

2. **The engine guardrail.**  A real one-replica cluster on the smoke model
   serves the same workload at TP 1/2/4/8.  TP is a pricing change, not an
   execution change, so token streams must be byte-identical across the
   ladder; weight/KV movement rides ``p2p_*`` op classes priced at
   ``fabric_p2p_bw`` with ZERO fabric bytes on bridge channels (the
   conformance checker's structural P2P law), only CVM ingress pays the
   bridge toll, and stall-attribution closure holds >= 0.99 with the new
   ``fabric_p2p`` cause in the taxonomy.

3. **Fallback repricing.**  The TP=4 tape replayed under
   ``ReplaySpec(fabric_up=False)``: the SAME p2p records reprice at the
   CC-compatible TCP fallback rate — the degradation a stale partition or
   lapsed attestation would have cost, computed without re-running anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from repro.configs.base import all_configs, get_config, smoke_config
from repro.core.bridge import B300, TPU_V5E, BridgeModel
from repro.core.compute import ComputeModel
from repro.trace import opclasses as oc

#: the TP ladder (one tenant partition shape per degree, §7.1 vocabulary)
TP_DEGREES = (1, 2, 4, 8)

#: modeled ladder operating point (nemotron-4-340b decode, CC-on B300)
LADDER_CONFIG = "nemotron-4-340b"
LADDER_BATCH = 8
LADDER_KV_LEN = 2048.0

#: relative tolerance for the BENCH_tp.json drift check
REL_TOL = 1e-9

DRIFT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_tp.json")

GB = 1e9


# ---------------------------------------------------------------------------------
# 1. modeled TP ladder (nemotron-4-340b)
# ---------------------------------------------------------------------------------


def ladder_table() -> list[dict]:
    """Per-TP-degree step economics for the 340B config on CC-on B300."""
    cfg = get_config(LADDER_CONFIG)
    bridge = BridgeModel(B300, cc_on=True)
    rows = []
    for tp in TP_DEGREES:
        cm = ComputeModel(cfg, bridge, tp_degree=tp)
        charge = cm.decode_charge([LADDER_KV_LEN] * LADDER_BATCH)
        ar_bytes = cm.allreduce_bytes(LADDER_BATCH)
        ar_s = cm.allreduce_seconds(LADDER_BATCH, B300.fabric_p2p_bw)
        step_s = charge.seconds + ar_s
        rows.append({
            "tp": tp,
            "compute_ms": charge.seconds * 1e3,
            "allreduce_ms": ar_s * 1e3,
            "allreduce_mb": ar_bytes / 1e6,
            "step_ms": step_s * 1e3,
            "tok_s": LADDER_BATCH / step_s,
            "weights_per_device_gb":
                cm.active_params * cm.bytes_per_param / tp / GB,
            "bound": charge.bound,
        })
    return rows


# ---------------------------------------------------------------------------------
# 2. engine guardrail: real cluster serving across the ladder
# ---------------------------------------------------------------------------------


def _tp_run(tp: int) -> dict:
    """One single-replica cluster run at TP degree `tp` on the smoke model.

    Returns the sorted token streams plus the tape-level bridge/P2P byte
    attribution the guardrail asserts over.  The tape object rides along
    (stripped before the JSON payload) for the fallback-repricing section.
    """
    from repro.cluster import RoutingPolicy, build_cluster
    from repro.cluster.replica import ReplicaConfig
    from repro.obs.stalls import attribute_stalls
    from repro.serving.engine import Request
    from repro.serving.sampler import SamplingParams
    from repro.trace.conformance import check_tape

    model = _smoke_model()
    cluster = build_cluster(
        model, cc_on=True, n_replicas=1, partition_size=8,
        replica_cfg=ReplicaConfig(tp_degree=tp),
        routing=RoutingPolicy.LEAST_LOADED, seed=0)
    try:
        for i in range(6):
            cluster.submit(Request(
                f"r{i}", prompt=list(range(1, 17)) + [40 + i] * 4,
                sampling=SamplingParams(max_new_tokens=3 + i % 4)))
        cluster.run()
        replica = cluster.replicas[0]
        tape = replica.tape()
        report = check_tape(tape)
        stalls = attribute_stalls(tape)
        return {
            "tp": tp,
            "finished": len(replica.engine.finished),
            "tokens": tuple(sorted(
                (r.request_id, tuple(int(t) for t in r.output_tokens))
                for r in replica.engine.finished)),
            "virtual_time_s": replica.clock.now,
            "bridge_bytes": tape.bridge_bytes(),
            "p2p_bytes": tape.p2p_bytes(),
            "p2p_seconds": tape.p2p_seconds(),
            "p2p_allreduce_records":
                tape.op_class_mix().get(oc.P2P_ALLREDUCE, 0),
            "p2p_fallbacks": replica.gateway.stats.p2p_fallback_crossings,
            "conformant": report.ok,
            "closure": stalls.closure,
            "_tape": tape,
        }
    finally:
        cluster.close()


_MODEL = None


def _smoke_model():
    global _MODEL
    if _MODEL is None:
        from repro.models.model import Model
        _MODEL = Model(smoke_config(all_configs()["olmo-1b"]))
    return _MODEL


def engine_guardrail() -> tuple[list[dict], dict]:
    """TP ladder on the real engine; returns (json rows, tp4 run w/ tape)."""
    runs = [_tp_run(tp) for tp in TP_DEGREES]
    base = runs[0]
    tp4 = next(r for r in runs if r["tp"] == 4)
    rows = []
    for r in runs:
        rows.append({k: v for k, v in r.items()
                     if k not in ("tokens", "_tape")}
                    | {"tokens_identical": r["tokens"] == base["tokens"]})
    return rows, tp4


# ---------------------------------------------------------------------------------
# 3. fallback repricing (the same records, fabric down)
# ---------------------------------------------------------------------------------


def fallback_table(tp4_run: dict) -> dict:
    """Reprice the TP=4 tape's p2p records with the fabric forced down."""
    from repro.trace.replay import ReplaySpec, TraceReplayer

    tape = tp4_run["_tape"]
    replayer = TraceReplayer(tape)
    up = replayer.reprice(ReplaySpec(fabric_up=True))
    down = replayer.reprice(ReplaySpec(fabric_up=False))
    p2p_bytes = tape.p2p_bytes()
    return {
        "p2p_bytes": p2p_bytes,
        "fabric_up_s": up.total_replayed_s,
        "fabric_down_s": down.total_replayed_s,
        "p2p_penalty_s": down.total_replayed_s - up.total_replayed_s,
        # the structural expectation: the delta is exactly the same bytes at
        # the two rates — nothing else in the tape moves with the lever
        "expected_penalty_s":
            p2p_bytes / TPU_V5E.fabric_fallback_bw
            - p2p_bytes / TPU_V5E.fabric_p2p_bw,
    }


def payload() -> dict:
    """The deterministic drift payload: all three tables, virtual-clock only."""
    engine_rows, tp4 = engine_guardrail()
    return {
        "ladder": ladder_table(),
        "engine": engine_rows,
        "fallback": fallback_table(tp4),
    }


def run() -> list[str]:
    data = payload()
    lines = []
    by_tp = {r["tp"]: r for r in data["ladder"]}
    for r in data["ladder"]:
        lines.append(
            f"tp/ladder_{LADDER_CONFIG}_tp{r['tp']},{r['tok_s']:.1f},"
            f"tok/s at batch={LADDER_BATCH} kv={LADDER_KV_LEN:g} "
            f"({r['weights_per_device_gb']:.0f} GB weights/device, "
            f"allreduce {r['allreduce_ms']:.3f} ms of "
            f"{r['step_ms']:.3f} ms step)")
    if by_tp[4]["tok_s"] < by_tp[1]["tok_s"]:
        raise AssertionError(
            f"TP=4 modeled tok/s ({by_tp[4]['tok_s']:.1f}) fell below TP=1 "
            f"({by_tp[1]['tok_s']:.1f}) on {LADDER_CONFIG}")
    base_bridge = data["engine"][0]["bridge_bytes"]
    for e in data["engine"]:
        lines.append(
            f"tp/engine_tp{e['tp']}_bytes,{e['p2p_bytes']},"
            f"P2P bytes vs {e['bridge_bytes']} bridge bytes "
            f"({e['p2p_allreduce_records']} allreduce records, "
            f"closure {e['closure']:.4f})")
        if not e["tokens_identical"]:
            raise AssertionError(
                f"TP={e['tp']} token stream diverged from TP=1")
        if not e["conformant"]:
            raise AssertionError(
                f"TP={e['tp']} tape failed conformance (P2P law)")
        if e["bridge_bytes"] != base_bridge:
            raise AssertionError(
                f"TP={e['tp']} moved {e['bridge_bytes']} bridge bytes vs "
                f"{base_bridge} at TP=1 — only CVM ingress may pay the toll")
        if e["closure"] < 0.99:
            raise AssertionError(
                f"TP={e['tp']} stall-attribution closure {e['closure']:.4f} "
                f"< 0.99")
        if e["tp"] == 1 and e["p2p_bytes"] != 0:
            raise AssertionError("TP=1 emitted P2P traffic")
        if e["tp"] > 1 and e["p2p_bytes"] == 0:
            raise AssertionError(f"TP={e['tp']} emitted no P2P traffic")
        if e["p2p_fallbacks"] != 0:
            raise AssertionError(
                f"healthy attested run took {e['p2p_fallbacks']} fallbacks")
    fb = data["fallback"]
    lines.append(
        f"tp/fallback_penalty_s,{fb['p2p_penalty_s']:.6f},"
        f"repricing {fb['p2p_bytes']} P2P bytes at the TCP fallback "
        f"(fabric {fb['fabric_up_s']:.6f} s -> down {fb['fabric_down_s']:.6f} s)")
    if not _close(fb["p2p_penalty_s"], fb["expected_penalty_s"]):
        raise AssertionError(
            f"fallback repricing moved more than the P2P records: "
            f"penalty {fb['p2p_penalty_s']} != expected "
            f"{fb['expected_penalty_s']}")
    identical = all(e["tokens_identical"] for e in data["engine"])
    lines.append(
        f"tp/tokens_identical,{float(identical):.1f},"
        f"token streams byte-identical across TP 1/2/4/8 (greedy)")
    lines.append(
        f"tp/tp4_beats_tp1,"
        f"{float(by_tp[4]['tok_s'] >= by_tp[1]['tok_s']):.1f},"
        f"modeled 340B tok/s: TP=4 {by_tp[4]['tok_s']:.1f} >= "
        f"TP=1 {by_tp[1]['tok_s']:.1f}")
    return lines


# ---------------------------------------------------------------------------------
# BENCH_tp.json drift gate
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
    _diff_rows("ladder", golden.get("ladder", []), fresh["ladder"],
               ("tp",), problems)
    _diff_rows("engine", golden.get("engine", []), fresh["engine"],
               ("tp",), problems)
    _diff_rows("fallback", [golden.get("fallback", {})], [fresh["fallback"]],
               (), problems)
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
            print("BENCH_tp.json is stale — regenerate with "
                  "`python -m benchmarks.bench_tp --write` and review:")
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
