"""bridge_opt ablation ladder: arena x coalescer x pipelined restore,
plus the compute-charged §5.5 scheduling ladder and the overlap guardrail.

Part 1 (PR 3): one real engine workload on the B300 CC-on profile, run
under the vLLM-default discipline (ASYNC_OVERLAP — the paper's degraded
baseline), then re-run with the transfer-optimization subsystem enabled
rung by rung:

  all_off          fresh staging per small crossing (the 44x class)
  coalescer        sub-threshold crossings fuse; flush buffers first-touch
  arena_coalescer  flush buffers come from the budgeted staging arena
  all_on           arena prewarmed + pipelined chunked KV restore

Part 2 (ISSUE 4): the same engine with decode/prefill compute charged to
the virtual clock — priced against the paper's own serving config
(qwen3.6-27B on B300, the §5.2 profiling cell) while executing the smoke
model — swept across concurrency under the paper's §5.4–§5.5 recovery
ladder:

  async_base        vLLM default (fresh staging, blocking "non-blocking")
  sched_flag        the one-flag fix (SYNC_DRAIN; paper recovers 57%)
  worker_coalescer  worker drain composed with the coalescer (paper's v10c
                    recovers up to 92% at high concurrency)

Gold is always the async_base stream re-priced CC-off by TraceReplayer
(the §5.2 method, never a second noisy run); recovered fractions are of
the modeled CC gap *including* compute, which is what makes the ladder a
statement about hideability rather than about toll arithmetic.  The
deadline-flush count is reported because it is the observable proof that
the coalescer's latency bound is now driven by compute charges.

Part 3: the restore-overlap guardrail — the same workload with a pipelined
restore in flight, overlap preference on vs off; on must never lose.

Part 4 (ISSUE 5): the restore-under-decode sweep for slot-masked decode.
Four slots decode while one slot's pipelined restore drains mid-run; the
ready slots keep stepping and the restoring slot rejoins when its pipeline
lands.  The output tokens must equal those of the same workload with no
restore, and both tapes must conform.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import get_config
from repro.core.bridge import B300, BridgeModel
from repro.core.compute import ComputeModel
from repro.obs import CAUSE_FLUSH, CAUSE_FRESH, CAUSE_SERIAL, attribute_stalls
from repro.core.policy import (OffloadPolicy, RuntimeDefaults,
                               SchedulingPolicy as SP, cc_aware_defaults)
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.sampler import SamplingParams
from repro.trace import ReplaySpec, TraceRecorder, TraceReplayer, check_tape
from repro.trace.harness import smoke_model

#: the fixed ablation workload (decode phase)
N_REQUESTS = 6
MAX_NEW_TOKENS = 8
MAX_BATCH = 4
PROMPT = (1, 2, 3)

#: the restore phase: a warm prefix restored through the same gateway
RESTORE_BLOCKS = 48
BLOCK_BYTES = 128 << 10
RESTORE_CHUNK_BYTES = 256 << 10

ARENA_BYTES = 64 << 20

#: strict ladder order for the fresh-share monotonicity claim
LADDER = ["all_off", "coalescer", "arena_coalescer", "all_on"]

VARIANTS = {
    # name -> (arena_bytes, prewarm_arena, coalesce, pipelined_restore)
    "all_off": (0, False, False, False),
    "coalescer": (0, False, True, False),
    "arena": (ARENA_BYTES, False, False, False),
    "arena_coalescer": (ARENA_BYTES, False, True, False),
    "all_on": (ARENA_BYTES, True, True, True),
}


def _defaults(arena_bytes: int, coalesce: bool, pipelined: bool) -> RuntimeDefaults:
    return RuntimeDefaults(
        scheduling=SP.ASYNC_OVERLAP,
        offload=OffloadPolicy.REUSE_AWARE,
        store_threshold=2,
        loader_pool_workers=8,
        loader_prewarm=True,
        batch_small_crossings=False,
        staging_arena_bytes=arena_bytes,
        coalesce_small_crossings=coalesce,
        pipelined_restore=pipelined,
    )


def run_variant(model, name: str) -> dict:
    arena_bytes, prewarm, coalesce, pipelined = VARIANTS[name]
    engine = ServingEngine(
        model, max_batch=MAX_BATCH, max_len=64,
        policy=SP.ASYNC_OVERLAP,
        bridge=BridgeModel(B300, cc_on=True),
        defaults=_defaults(arena_bytes, coalesce, pipelined), seed=0)
    gw = engine.gateway
    gw.pool.prewarm()    # channel lifecycle off the critical path (§6.1)
    if prewarm and gw.arena is not None:
        # pin the classes the workload touches before it starts: the fully
        # disciplined runtime has no first-touch FRESH crossings at all
        gw.arena.prewarm([64, 128, 256,
                          engine.coalescer.watermark_bytes
                          if engine.coalescer else 256])
    recorder = TraceRecorder(gw, policy=SP.ASYNC_OVERLAP.value,
                             label=f"bridge_opt-{name}").attach()
    try:
        for i in range(N_REQUESTS):
            engine.submit(Request(
                f"r{i}", prompt=list(PROMPT),
                sampling=SamplingParams(max_new_tokens=MAX_NEW_TOKENS)))
        engine.run()
        decode_s = gw.stats.bridge_time_s

        mgr = OffloadManager(
            gw, OffloadPolicy.REUSE_AWARE,
            coalescer=engine.coalescer,
            pipelined_restore=pipelined,
            restore_chunk_bytes=RESTORE_CHUNK_BYTES)
        for b in range(RESTORE_BLOCKS):
            mgr.host_store[b] = HostBlock(b, BLOCK_BYTES, 2, None)
        mgr.restore(list(range(RESTORE_BLOCKS)))
        if engine.coalescer is not None:
            engine.coalescer.barrier()
        restore_s = gw.stats.bridge_time_s - decode_s
        tape = recorder.tape()
    finally:
        recorder.detach()
        engine.close()
    return {
        "decode_s": decode_s,
        "restore_s": restore_s,
        "total_s": gw.stats.bridge_time_s,
        "tape": tape,
        "fresh_share": tape.fresh_share(),
        "arena": gw.arena.stats_dict() if gw.arena is not None else None,
        "coalescer_saved": (engine.coalescer.stats.crossings_saved
                            if engine.coalescer is not None else 0),
        "restore_overlap_s": mgr.stats.restore_overlap_s,
        "conformance_ok": check_tape(tape).ok,
    }


# ---------------------------------------------------------------------------------
# Part 2: the compute-charged §5.5 scheduling ladder (ISSUE 4)
# ---------------------------------------------------------------------------------

#: the paper's own serving config prices the compute side of every step
PAPER_MODEL = "qwen3p6-27b"
#: concurrency sweep (batch slots, all kept busy); the §5.5 claim is at the
#: high end — worker composition amortizes, the one-flag fix does not
LADDER_CONCURRENCY = (2, 8)


def run_ladder_variant(model, policy: SP, *, concurrency: int,
                       bridge_opt: bool) -> dict:
    """One compute-charged engine run under `policy` at `concurrency`."""
    bridge = BridgeModel(B300, cc_on=True)
    defaults = dataclasses.replace(
        _defaults(ARENA_BYTES if bridge_opt else 0, bridge_opt, False),
        scheduling=policy)
    engine = ServingEngine(
        model, max_batch=concurrency, max_len=64, policy=policy,
        bridge=bridge, defaults=defaults,
        compute_model=ComputeModel(get_config(PAPER_MODEL), bridge),
        seed=0)
    gw = engine.gateway
    gw.pool.prewarm()
    recorder = TraceRecorder(
        gw, policy=policy.value,
        label=f"ladder-{policy.value}-c{concurrency}").attach()
    try:
        for i in range(concurrency):
            engine.submit(Request(
                f"r{i}", prompt=list(PROMPT),
                sampling=SamplingParams(max_new_tokens=MAX_NEW_TOKENS)))
        engine.run()
        tape = recorder.tape()
    finally:
        recorder.detach()
        engine.close()
    co = engine.coalescer
    return {
        "total_s": engine.clock.now,
        "tokens": sum(len(r.output_tokens) for r in engine.finished),
        "compute_s": gw.stats.compute_time_s,
        "bridge_s": gw.stats.bridge_time_s,
        "tape": tape,
        "deadline_flushes": co.stats.deadline_flushes if co else 0,
        "worker_flushes": co.stats.worker_flushes if co else 0,
        "conformance_ok": check_tape(tape).ok,
    }


def scheduling_ladder_rows(model) -> list[str]:
    """§5.4/§5.5 against a clock that charges compute: the one-flag fix
    recovers >= 0.57 of the modeled CC gap; worker x coalescer strictly
    more at high concurrency, with the deadline trigger observably firing."""
    lines = []
    recovered = {}
    high_c = max(LADDER_CONCURRENCY)
    for c in LADDER_CONCURRENCY:
        base = run_ladder_variant(model, SP.ASYNC_OVERLAP,
                                  concurrency=c, bridge_opt=False)
        sched = run_ladder_variant(model, SP.SYNC_DRAIN,
                                   concurrency=c, bridge_opt=False)
        worker = run_ladder_variant(model, SP.WORKER_DRAIN,
                                    concurrency=c, bridge_opt=True)
        gold = TraceReplayer(base["tape"]).reprice(
            ReplaySpec(cc_on=False)).total_replayed_s
        gap = base["total_s"] - gold
        recovered[c] = {
            "sched_flag": (base["total_s"] - sched["total_s"]) / max(gap, 1e-12),
            "worker_coalescer": (base["total_s"] - worker["total_s"]) / max(gap, 1e-12),
        }
        lines.append(
            f"bridge_opt/ladder_c{c}_base_total_s,{base['total_s']:.6f},"
            f"compute={base['compute_s']:.4f}s bridge={base['bridge_s']:.4f}s "
            f"gold={gold:.6f}s ({PAPER_MODEL}-priced compute)")
        lines.append(
            f"bridge_opt/ladder_c{c}_sched_flag_recovered,"
            f"{recovered[c]['sched_flag']:.4f},paper=0.57 (one-flag fix)")
        lines.append(
            f"bridge_opt/ladder_c{c}_worker_coalescer_recovered,"
            f"{recovered[c]['worker_coalescer']:.4f},"
            f"paper=up to 0.92 (v10c; worker flushes the D2H queue: "
            f"{worker['worker_flushes']} fused drains off the engine clock)")
        if c == high_c:
            lines.append(
                f"bridge_opt/ladder_deadline_flushes,"
                f"{float(worker['deadline_flushes']):.1f},"
                f"coalescer latency bound driven by compute charges "
                f"(must be > 0)")
            lines.append(
                f"bridge_opt/ladder_conformance_pass,"
                f"{float(all(v['conformance_ok'] for v in (base, sched, worker))):.1f},"
                f"L1-L4 + compute/crossing edge over all c={c} rung tapes")
    ordered = (recovered[high_c]["worker_coalescer"]
               > recovered[high_c]["sched_flag"])
    lines.append(
        f"bridge_opt/ladder_worker_beats_flag_at_high_c,{float(ordered):.1f},"
        f"c={high_c}: worker {recovered[high_c]['worker_coalescer']:.4f} vs "
        f"flag {recovered[high_c]['sched_flag']:.4f} (strictly more)")
    return lines


# ---------------------------------------------------------------------------------
# Part 3: restore-overlap guardrail (overlap-on must never lose)
# ---------------------------------------------------------------------------------

#: guardrail restore shape: a warm prefix deep enough that its pipeline
#: drain outlasts one 27B decode step (toll-dominated small chunks), so the
#: overlap preference has a window actually worth scheduling into
GUARDRAIL_BLOCKS = 96
GUARDRAIL_BLOCK_BYTES = 128 << 10
GUARDRAIL_CHUNK_BYTES = 8 << 10


def overlap_guardrail_rows(model) -> list[str]:
    def run_once(prefer: bool) -> dict:
        bridge = BridgeModel(B300, cc_on=True)
        defaults = dataclasses.replace(
            _defaults(ARENA_BYTES, True, True), overlap_scheduler=prefer)
        engine = ServingEngine(
            model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge, defaults=defaults,
            compute_model=ComputeModel(get_config(PAPER_MODEL), bridge),
            seed=0)
        gw = engine.gateway
        gw.pool.prewarm()
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             pipelined_restore=True,
                             restore_chunk_bytes=GUARDRAIL_CHUNK_BYTES)
        for b in range(GUARDRAIL_BLOCKS):
            mgr.host_store[b] = HostBlock(b, GUARDRAIL_BLOCK_BYTES, 2, None)
        mgr.restore(list(range(GUARDRAIL_BLOCKS)))
        engine.mark_restore("warm", mgr.last_restore_done_t)
        for rid in ("warm", "cold"):
            engine.submit(Request(
                rid, prompt=list(PROMPT),
                sampling=SamplingParams(max_new_tokens=MAX_NEW_TOKENS)))
        stats = engine.run()
        engine.close()
        return stats

    on, off = run_once(True), run_once(False)
    tps_on = on["total_tokens"] / max(on["virtual_time_s"], 1e-12)
    tps_off = off["total_tokens"] / max(off["virtual_time_s"], 1e-12)
    return [
        f"bridge_opt/overlap_on_decode_tps,{tps_on:.4f},"
        f"restore window filled with decode compute "
        f"(deferred={on['overlap']['deferred_admissions']}, "
        f"barrier_wait={on['overlap']['barrier_wait_s']:.6f}s)",
        f"bridge_opt/overlap_off_decode_tps,{tps_off:.4f},"
        f"restore window paid as idle barrier wait "
        f"(barrier_wait={off['overlap']['barrier_wait_s']:.6f}s)",
        f"bridge_opt/overlap_guardrail_ok,{float(tps_on >= tps_off):.1f},"
        f"overlap-on decode throughput must never lose under CC-on defaults",
    ]


# ---------------------------------------------------------------------------------
# Part 4: restore-under-decode sweep (slot-masked decode, ISSUE 5)
# ---------------------------------------------------------------------------------

#: the restoring slot's short tail vs the batch's long one: masking can hide
#: the whole drain window inside the others' runway, the barrier cannot
MASKED_SWEEP_SHORT_TOKENS = 4
MASKED_SWEEP_LONG_TOKENS = 16
MASKED_SWEEP_BLOCKS = 96
MASKED_SWEEP_BLOCK_BYTES = 128 << 10
MASKED_SWEEP_CHUNK_BYTES = 8 << 10


def slot_masked_rows(model) -> list[str]:
    def run_once(restore: bool) -> dict:
        bridge = BridgeModel(B300, cc_on=True)
        defaults = dataclasses.replace(
            _defaults(ARENA_BYTES, True, True), scheduling=SP.SYNC_DRAIN)
        engine = ServingEngine(
            model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge, defaults=defaults,
            compute_model=ComputeModel(get_config(PAPER_MODEL), bridge),
            seed=0)
        gw = engine.gateway
        gw.pool.prewarm()
        engine.submit(Request(
            "r0", prompt=list(PROMPT),
            sampling=SamplingParams(max_new_tokens=MASKED_SWEEP_SHORT_TOKENS)))
        for i in range(1, 4):
            engine.submit(Request(
                f"r{i}", prompt=list(PROMPT),
                sampling=SamplingParams(
                    max_new_tokens=MASKED_SWEEP_LONG_TOKENS)))
        engine.step()      # all four slots running
        # r0's restore pipeline starts draining mid-decode (the late-restore
        # shape: re-restore after migration/preemption), notified through
        # the offload layer's per-request completion callback
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             pipelined_restore=True,
                             restore_chunk_bytes=MASKED_SWEEP_CHUNK_BYTES)
        for b in range(MASKED_SWEEP_BLOCKS):
            mgr.host_store[b] = HostBlock(b, MASKED_SWEEP_BLOCK_BYTES, 2, None)
        mgr.on_restore_done.append(engine.mark_restore)
        recorder = TraceRecorder(gw, policy=SP.SYNC_DRAIN.value,
                                 label=f"slot-masked-{restore}").attach()
        try:
            if restore:
                mgr.restore(list(range(MASKED_SWEEP_BLOCKS)), key="r0")
            stats = engine.run()
        finally:
            recorder.detach()
            engine.close()
        return {
            "stats": stats,
            "tps": stats["total_tokens"] / max(stats["virtual_time_s"], 1e-12),
            "tokens": {r.request_id: list(r.output_tokens)
                       for r in engine.finished},
            "summary": recorder.summary(),
            "conformance_ok": check_tape(recorder.tape()).ok,
        }

    on, free = run_once(True), run_once(False)
    ov_on = on["stats"]["overlap"]
    return [
        f"bridge_opt/restore_under_decode_tps,{on['tps']:.4f},"
        f"ready slots keep stepping while r0's pipeline drains "
        f"(deferred_slots={ov_on['deferred_slots']}, "
        f"deferred_slot_steps={on['summary']['deferred_slot_steps']}, "
        f"barrier_wait={ov_on['barrier_wait_s']:.6f}s)",
        f"bridge_opt/slot_masked_tokens_identical,"
        f"{float(on['tokens'] == free['tokens']):.1f},"
        f"masking changes timing, never tokens (greedy rejoin; against the "
        f"restore-free run)",
        f"bridge_opt/slot_masked_conformance_pass,"
        f"{float(on['conformance_ok'] and free['conformance_ok']):.1f},"
        f"L1-L4 + compute/crossing edge over both sweep tapes",
    ]


def run() -> list[str]:
    model = smoke_model()
    results = {name: run_variant(model, name) for name in VARIANTS}
    base = results["all_off"]
    full = results["all_on"]

    # gold: the all_off stream itself, re-priced CC-off (§5.2 method)
    gold = TraceReplayer(base["tape"]).reprice(
        ReplaySpec(cc_on=False)).total_replayed_s
    gap = base["total_s"] - gold

    lines = []
    for name in VARIANTS:
        r = results[name]
        recovered = (base["total_s"] - r["total_s"]) / max(gap, 1e-12)
        lines.append(
            f"bridge_opt/{name}_bridge_s,{r['total_s']:.6f},"
            f"decode={r['decode_s']:.6f}s restore={r['restore_s']:.6f}s "
            f"recovered={recovered:.3f} of CC gap")
    for name in LADDER:
        lines.append(
            f"bridge_opt/{name}_fresh_share,{results[name]['fresh_share']:.6f},"
            f"fresh-staging share of recorded tape seconds (SS5.2 class)")

    shares = [results[n]["fresh_share"] for n in LADDER]
    monotone = all(a > b for a, b in zip(shares, shares[1:]))
    lines.append(
        f"bridge_opt/fresh_share_strictly_decreasing,{float(monotone):.4f},"
        f"ladder {' > '.join(f'{s:.3f}' for s in shares)} "
        f"(arena+coalescer remove exactly the 44x class)")

    recovered_full = (base["total_s"] - full["total_s"]) / max(gap, 1e-12)
    lines.append(
        f"bridge_opt/full_recovered_fraction,{recovered_full:.4f},"
        f"paper recovery ladder: 0.57 (sched flag) / 0.92 (worker drain); "
        f"gap={gap:.4f}s gold={gold:.6f}s")

    # CI perf guardrail inputs: coalesced decode must beat the uncoalesced
    # baselines (both the fresh async path and the arena-staged path)
    lines.append(
        f"bridge_opt/decode_bridge_time_uncoalesced_s,"
        f"{results['arena']['decode_s']:.6f},"
        f"arena-staged, per-crossing tolls (async fresh path: "
        f"{base['decode_s']:.6f}s)")
    lines.append(
        f"bridge_opt/decode_bridge_time_coalesced_s,"
        f"{results['arena_coalescer']['decode_s']:.6f},"
        f"fused flushes; must be <= uncoalesced")

    arena_stats = full["arena"]
    lines.append(
        f"bridge_opt/arena_hit_rate,{arena_stats['hit_rate']:.4f},"
        f"hits={arena_stats['hits']} misses={arena_stats['misses']} "
        f"pinned={arena_stats['pinned_bytes']}B "
        f"high_water={arena_stats['high_water_bytes']}B "
        f"cap={arena_stats['capacity_bytes']}B")
    lines.append(
        f"bridge_opt/coalescer_crossings_saved,{full['coalescer_saved']:.4f},"
        f"tolls avoided by fusing sub-threshold crossings")

    # the +131% KV-restore penalty, attacked: blocking drain vs pipeline fill
    blocking = base["restore_s"]
    pipelined = full["restore_s"]
    lines.append(
        f"bridge_opt/restore_blocking_s,{blocking:.6f},"
        f"whole-prefix blocking drain (the +131% penalty shape)")
    lines.append(
        f"bridge_opt/restore_pipelined_s,{pipelined:.6f},"
        f"pipeline fill only; overlap={full['restore_overlap_s']:.6f}s "
        f"moved off the critical path")
    lines.append(
        f"bridge_opt/restore_speedup_x,{blocking / max(pipelined, 1e-12):.4f},"
        f"chunked double-buffered restore vs blocking drain")

    conf_ok = all(r["conformance_ok"] for r in results.values())
    lines.append(
        f"bridge_opt/conformance_pass,{float(conf_ok):.4f},"
        f"L1-L4 over all {len(results)} rung tapes")

    # ---- stall attribution over the ablation ladder (DESIGN.md §9) --------
    # every rung's tape decomposes its bridge-vs-compute gap into the §5.2
    # cause vocabulary; conservation is exact by construction, and closure
    # (the share NOT left as unattributed idle) is the CI-gated quality bar
    reports = {name: attribute_stalls(results[name]["tape"])
               for name in LADDER}
    for name in LADDER:
        rep = reports[name]
        lines.append(
            f"obs/{name}_stall_closure,{rep.closure:.6f},"
            f"gap={rep.gap_s:.6f}s "
            f"fresh={rep.causes.get(CAUSE_FRESH, 0.0):.6f}s "
            f"serial={rep.causes.get(CAUSE_SERIAL, 0.0):.6f}s "
            f"flush={rep.causes.get(CAUSE_FLUSH, 0.0):.6f}s")
    closure_min = min(rep.closure for rep in reports.values())
    lines.append(
        f"obs/closure_min,{closure_min:.6f},"
        f"min over the {'->'.join(LADDER)} ladder; attributed stall "
        f"seconds must cover >= 0.99 of each tape's bridge-vs-compute gap")
    # the ladder's observability story: each rung removes a cause — fresh
    # tolls fall as the arena pins staging, serialization falls as the
    # coalescer fuses crossings.  Non-increasing with a 100 ns epsilon:
    # rungs that don't touch a cause tie it up to float noise (the
    # pipelined-restore record layout shifts the residual by ~2 ns), and
    # the real per-rung deltas are tens of microseconds and up
    eps = 1e-7
    fresh = [reports[n].causes.get(CAUSE_FRESH, 0.0) for n in LADDER]
    serial = [reports[n].causes.get(CAUSE_SERIAL, 0.0) for n in LADDER]
    fresh_mono = all(a >= b - eps for a, b in zip(fresh, fresh[1:]))
    serial_mono = all(a >= b - eps for a, b in zip(serial, serial[1:]))
    lines.append(
        f"obs/fresh_toll_monotone,{float(fresh_mono):.1f},"
        f"fresh-staging stall seconds non-increasing along the ladder "
        f"({' >= '.join(f'{s:.6f}' for s in fresh)})")
    lines.append(
        f"obs/serialization_monotone,{float(serial_mono):.1f},"
        f"channel-serialization stall seconds non-increasing along the "
        f"ladder ({' >= '.join(f'{s:.6f}' for s in serial)})")
    lines.extend(scheduling_ladder_rows(model))
    lines.extend(overlap_guardrail_rows(model))
    lines.extend(slot_masked_rows(model))
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
