"""Quantized bridge crossings benchmark: narrower wire, same tokens.

Three payloads, one module (DESIGN.md §13):

1. **Restore-under-decode tok/s.**  The bench_bridge_opt restore-under-
   decode shape — four slots decoding while one slot's pipelined KV
   restore drains through the same serialized bridge — run full-width
   (bf16) and again with ``kv_quant="fp8"``.  The quantized run must move
   <= 0.55x the bridge bytes (fp8 values + per-block scales vs bf16) and
   finish STRICTLY more virtual tok/s, with identical output tokens: the
   codec changes wire width and timing, never sampling.

2. **The counterfactual un-quantize replay gate.**  A bulk (pool=1)
   quantized offload run's tape, re-priced by ``TraceReplayer`` with
   ``ReplaySpec(quantize="")`` — crossings widened back to ``raw_bytes``,
   dequant compute dropped — must land within 2% of the *recorded*
   full-width run of the same workload.  That closes the §5.2 loop for
   quantization exactly as it already holds for scheduling and staging
   levers: the tape carries enough truth to price the path not taken.
   The forced-quantize lever (``quantize="fp8"`` on the full-width tape)
   is reported as the optimistic what-if in the other direction.

3. **Weight-only shard loads.**  ``PooledLoader`` over a real sharded
   f32 checkpoint with and without ``weight_quant="int8"``: staging,
   bridge transfer and assembly are all byte-rated (3.88x fewer bytes),
   so the modeled load time must drop by more than the dequant widening
   costs (~1.5x end to end on the PREWARMED pool, where the per-shard
   tolls are the only fixed charge left).

Pure virtual-clock arithmetic end to end: bit-deterministic, checked into
``BENCH_quant.json`` (CI drift gate: ``python -m benchmarks.bench_quant
--check``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from repro.configs.base import get_config
from repro.core.bridge import B300, BridgeModel
from repro.core.compute import ComputeModel
from repro.core.gateway import TransferGateway
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.loader.pooled_loader import LoaderVariant, PooledLoader
from repro.loader.sharded_weights import ShardedCheckpoint, save_sharded
from repro.quant import wire_bytes as quant_wire
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.sampler import SamplingParams
from repro.trace import (ReplaySpec, TraceRecorder, TraceReplayer,
                         check_tape)
from repro.trace import opclasses as oc
from repro.trace.harness import smoke_model

#: the paper's serving config prices decode compute and dequant widening
PAPER_MODEL = "qwen3p6-27b"

#: restore-under-decode workload (the bench_bridge_opt sweep shape)
PROMPT = (1, 2, 3)
SHORT_TOKENS = 4
LONG_TOKENS = 16
#: sized so the restore pipeline outlasts the decode phase — the wire
#: width is then ON the critical path (r0's restore barrier) and the
#: tok/s spread between bf16 and fp8 measures the bytes, not the overlap
RESTORE_BLOCKS = 96
BLOCK_BYTES = 1 << 20
CHUNK_BYTES = 64 << 10

#: bulk replay-gate workload: pool=1 + bulk restore, so recorded bridge
#: durations equal replay pricing and the 2% gate measures the lever alone
BULK_BLOCKS = 24
BULK_BLOCK_BYTES = 96 << 10

#: loader ladder checkpoint: 8 f32 tensors over 4 shards, loaded with the
#: PREWARMED pool so the byte-rated components (stage/transfer/assemble)
#: carry the total rather than the fixed channel-lifecycle charge
LOADER_TENSORS = 8
LOADER_SHAPE = (1024, 1024)
LOADER_SHARDS = 4

#: ISSUE acceptance gates
FP8_BYTE_RATIO_MAX = 0.55
REPLAY_REL_ERR_MAX = 0.02

#: relative tolerance for the BENCH_quant.json drift check
REL_TOL = 1e-9

DRIFT_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_quant.json")

_RESTORE_CLASSES = frozenset({oc.KV_RESTORE_H2D, oc.KV_RESTORE_PIPELINED,
                              oc.KV_RESTORE_Q})


# ---------------------------------------------------------------------------------
# Part 1: restore-under-decode, full-width vs fp8
# ---------------------------------------------------------------------------------


def run_restore(model, kv_quant: str) -> dict:
    bridge = BridgeModel(B300, cc_on=True)
    defaults = dataclasses.replace(
        cc_aware_defaults(True), scheduling=SP.SYNC_DRAIN,
        loader_pool_workers=8, pipelined_restore=True, kv_quant=kv_quant)
    compute = ComputeModel(get_config(PAPER_MODEL), bridge)
    engine = ServingEngine(model, max_batch=4, max_len=64,
                           policy=SP.SYNC_DRAIN, bridge=bridge,
                           defaults=defaults, compute_model=compute, seed=0)
    gw = engine.gateway
    gw.pool.prewarm()
    # the RESTORING request is the long one: the run's tail is then
    # restore-done + r0's remaining decode, so the wire width is on the
    # critical path rather than hidden under unrelated long decoders
    engine.submit(Request(
        "r0", prompt=list(PROMPT),
        sampling=SamplingParams(max_new_tokens=LONG_TOKENS)))
    for i in range(1, 4):
        engine.submit(Request(
            f"r{i}", prompt=list(PROMPT),
            sampling=SamplingParams(max_new_tokens=SHORT_TOKENS)))
    engine.step()      # all four slots running before the restore lands
    mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                         pipelined_restore=True,
                         restore_chunk_bytes=CHUNK_BYTES,
                         kv_quant=kv_quant, compute_model=compute)
    # seed the host store the way a prior spill phase would have left it:
    # full-width blocks, or wire-width + codec when the spill was quantized
    wire = quant_wire(BLOCK_BYTES, itemsize=2) if kv_quant else 0
    for b in range(RESTORE_BLOCKS):
        mgr.host_store[b] = HostBlock(b, BLOCK_BYTES, 2, None,
                                      wire_bytes=wire, codec=kv_quant)
    mgr.on_restore_done.append(engine.mark_restore)
    recorder = TraceRecorder(gw, policy=SP.SYNC_DRAIN.value,
                             label=f"quant-restore-{kv_quant or 'bf16'}"
                             ).attach()
    try:
        mgr.restore(list(range(RESTORE_BLOCKS)), key="r0")
        stats = engine.run()
        tape = recorder.tape()
    finally:
        recorder.detach()
        engine.close()
    restore = [r for r in tape.records
               if r.kind == "crossing" and r.op_class in _RESTORE_CLASSES]
    return {
        "kv_quant": kv_quant or "bf16",
        "tok_s": stats["total_tokens"] / max(stats["virtual_time_s"], 1e-12),
        "virtual_time_s": stats["virtual_time_s"],
        "restore_wire_bytes": sum(r.nbytes for r in restore),
        "restore_raw_bytes": sum(r.raw_bytes or r.nbytes for r in restore),
        "dequant_s": sum(r.t_end - r.t_start for r in tape.records
                         if r.op_class == oc.DEQUANT_COMPUTE),
        "tokens": {r.request_id: list(r.output_tokens)
                   for r in engine.finished},
        "conformance_ok": check_tape(tape).ok,
    }


# ---------------------------------------------------------------------------------
# Part 2: the un-quantize replay gate (bulk, pool=1: recorded == priced)
# ---------------------------------------------------------------------------------


def run_bulk(kv_quant: str):
    """One spill+restore workload through a pool-1 gateway; returns the
    tape and the recorded bridge seconds."""
    bridge = BridgeModel(B300, cc_on=True)
    gw = TransferGateway(bridge, cc_aware_defaults(True), pool_workers=1)
    compute = ComputeModel(get_config(PAPER_MODEL), bridge)
    recorder = TraceRecorder(gw, policy="sync_drain",
                             label=f"quant-bulk-{kv_quant or 'bf16'}"
                             ).attach()
    try:
        mgr = OffloadManager(gw, OffloadPolicy.SPILL_ALL,
                             kv_quant=kv_quant, compute_model=compute)
        for b in range(BULK_BLOCKS):
            mgr.evict(b, payload_bytes=BULK_BLOCK_BYTES)
        mgr.restore(list(range(BULK_BLOCKS)), key="bulk")
        tape = recorder.tape()
    finally:
        recorder.detach()
    return tape, gw.stats.bridge_time_s


def replay_gate() -> dict:
    full_tape, full_recorded_s = run_bulk("")
    fp8_tape, fp8_recorded_s = run_bulk("fp8")
    assert check_tape(full_tape).ok and check_tape(fp8_tape).ok
    full_asrec = TraceReplayer(full_tape).reprice(
        ReplaySpec()).total_replayed_s
    unquant = TraceReplayer(fp8_tape).reprice(
        ReplaySpec(quantize="")).total_replayed_s
    forced = TraceReplayer(full_tape).reprice(
        ReplaySpec(quantize="fp8")).total_replayed_s
    return {
        "full_recorded_s": full_recorded_s,
        "fp8_recorded_s": fp8_recorded_s,
        "full_asrec_replay_s": full_asrec,
        "unquant_replay_s": unquant,
        "forced_fp8_replay_s": forced,
        "unquant_rel_err": abs(unquant - full_asrec) / full_asrec,
        "fp8_byte_ratio": (fp8_tape.bridge_bytes()
                           / fp8_tape.bridge_raw_bytes()),
        "fp8_bridge_bytes": fp8_tape.bridge_bytes(),
        "full_bridge_bytes": full_tape.bridge_bytes(),
    }


# ---------------------------------------------------------------------------------
# Part 3: weight-only shard loads
# ---------------------------------------------------------------------------------


def loader_ladder() -> dict:
    rng = np.random.default_rng(0)
    tensors = {f"w{i}": rng.standard_normal(LOADER_SHAPE).astype(np.float32)
               for i in range(LOADER_TENSORS)}
    tmp = tempfile.mkdtemp(prefix="bench_quant_ckpt_")
    try:
        save_sharded(tmp, tensors, n_shards=LOADER_SHARDS)
        ckpt = ShardedCheckpoint(tmp)
        bridge = BridgeModel(B300, cc_on=True)
        full = PooledLoader(bridge, n_workers=8)
        _, full_bd = full.load(ckpt, LoaderVariant.PREWARMED)
        quant = PooledLoader(bridge, n_workers=8, weight_quant="int8")
        _, quant_bd = quant.load(ckpt, LoaderVariant.PREWARMED)
        wire = sum(quant.shard_wire_bytes(ckpt, s)
                   for s in range(ckpt.n_shards))
        return {
            "total_bytes": ckpt.total_bytes(),
            "wire_bytes": wire,
            "full_s": full_bd["total"],
            "int8_s": quant_bd["total"],
            "dequant_s": quant_bd["dequant"],
            "speedup": full_bd["total"] / quant_bd["total"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------------
# payload + drift gate
# ---------------------------------------------------------------------------------


def payload() -> dict:
    model = smoke_model()
    restore = [run_restore(model, ""), run_restore(model, "fp8")]
    tokens_identical = restore[0].pop("tokens") == restore[1].pop("tokens")
    return {
        "restore": restore,
        "tokens_identical": tokens_identical,
        "replay": replay_gate(),
        "loader": loader_ladder(),
    }


def run() -> list[str]:
    data = payload()
    bf16, fp8 = data["restore"]
    rep, ld = data["replay"], data["loader"]
    byte_ratio = fp8["restore_wire_bytes"] / bf16["restore_wire_bytes"]

    if not data["tokens_identical"]:
        raise AssertionError("fp8 KV restore changed the token stream")
    if byte_ratio > FP8_BYTE_RATIO_MAX:
        raise AssertionError(
            f"fp8 restore moved {byte_ratio:.4f}x the bf16 bridge bytes "
            f"(gate: <= {FP8_BYTE_RATIO_MAX})")
    if not fp8["tok_s"] > bf16["tok_s"]:
        raise AssertionError(
            f"quantized restore-under-decode must win strictly: "
            f"fp8 {fp8['tok_s']:.1f} vs bf16 {bf16['tok_s']:.1f} tok/s")
    if rep["unquant_rel_err"] > REPLAY_REL_ERR_MAX:
        raise AssertionError(
            f"un-quantize replay off by {rep['unquant_rel_err']:.4f} "
            f"from the recorded full-width run (gate: <= "
            f"{REPLAY_REL_ERR_MAX})")
    if not ld["speedup"] > 1.0:
        raise AssertionError(
            f"weight-only int8 load must beat full width: "
            f"{ld['speedup']:.3f}x")
    if not (bf16["conformance_ok"] and fp8["conformance_ok"]):
        raise AssertionError("restore sweep tape failed conformance")

    return [
        f"quant/restore_tok_s_bf16,{bf16['tok_s']:.4f},"
        f"full-width restore-under-decode "
        f"({bf16['restore_wire_bytes']} bridge bytes)",
        f"quant/restore_tok_s_fp8,{fp8['tok_s']:.4f},"
        f"fp8 restore-under-decode ({fp8['restore_wire_bytes']} bridge "
        f"bytes + {fp8['dequant_s']*1e3:.3f} ms dequant compute)",
        f"quant/restore_byte_ratio,{byte_ratio:.6f},"
        f"fp8 wire / bf16 wire on the same restore "
        f"(gate: <= {FP8_BYTE_RATIO_MAX}; values + per-block scales)",
        f"quant/restore_speedup,{fp8['tok_s']/bf16['tok_s']:.6f},"
        f"quantized tok/s over full width — must be STRICTLY > 1 while "
        f"the restore pipeline drains",
        f"quant/tokens_identical,{float(data['tokens_identical']):.1f},"
        f"the codec changes wire width and timing, never sampling",
        f"quant/replay_unquant_rel_err,{rep['unquant_rel_err']:.9f},"
        f"un-quantize replay {rep['unquant_replay_s']*1e3:.4f} ms vs "
        f"recorded full-width {rep['full_asrec_replay_s']*1e3:.4f} ms "
        f"(gate: <= {REPLAY_REL_ERR_MAX})",
        f"quant/replay_forced_fp8_saves,"
        f"{float(rep['forced_fp8_replay_s'] < rep['full_asrec_replay_s']):.1f},"
        f"forced-quantize what-if prices below the full-width recording "
        f"({rep['forced_fp8_replay_s']*1e3:.4f} ms, optimistic: no dequant)",
        f"quant/loader_int8_speedup,{ld['speedup']:.4f},"
        f"weight-only int8 shard loads: {ld['wire_bytes']} wire vs "
        f"{ld['total_bytes']} raw bytes, dequant {ld['dequant_s']*1e6:.2f} us",
        f"quant/conformance_pass,"
        f"{float(bf16['conformance_ok'] and fp8['conformance_ok']):.1f},"
        f"L1-L4 + Q (wire <= raw, codec named, tagged) over both sweeps",
    ]


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(abs(a), abs(b), 1e-30)


def _diff(kind: str, gold, fresh, problems: list) -> None:
    if isinstance(fresh, dict):
        for key, val in fresh.items():
            _diff(f"{kind}.{key}", (gold or {}).get(key), val, problems)
        return
    if isinstance(fresh, list):
        if not isinstance(gold, list) or len(gold) != len(fresh):
            problems.append(f"{kind} shape changed")
            return
        for i, (g, f_) in enumerate(zip(gold, fresh)):
            _diff(f"{kind}[{i}]", g, f_, problems)
        return
    ok = _close(fresh, gold) if isinstance(fresh, float) \
        and isinstance(gold, float) else fresh == gold
    if not ok:
        problems.append(f"{kind}: {gold!r} -> {fresh!r}")


def check_drift(path: str) -> list[str]:
    """Recompute the deterministic payload and diff it against `path`."""
    with open(path) as f:
        golden = json.load(f)
    problems: list[str] = []
    _diff("quant", golden, payload(), problems)
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
            print("BENCH_quant.json is stale — regenerate with "
                  "`python -m benchmarks.bench_quant --write` and review:")
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
