"""Compute-charged engine clock + restore-aware overlap scheduling.

The tests pin the overlap laws (ISSUE 4):

  * compute is a first-class virtual-clock charge, tape-visible as
    kind="compute" records that conform (L1-L4 + the compute/crossing
    no-overlap edge);
  * D2H drain staging goes through the StagingArena — first touch pays the
    fresh toll exactly once (the ROADMAP D2H-economics fix);
  * the coalescer's deadline trigger comes due off compute charges;
  * restore_barrier: a step that reads restored KV before the pipeline
    drains blocks to pipeline end; a step that doesn't, never pays it
    (pipelined and non-pipelined);
  * the overlap preference never loses: overlap-on decode throughput >=
    overlap-off under CC-on defaults (the CI guardrail, in-tree);
  * worker-drain x coalescer composition: fused D2H flushes ride a secure
    channel, not the engine clock.
"""

import dataclasses

import numpy as np
import pytest

from repro.bridge_opt import CrossingCoalescer, StagingArena
from repro.configs.base import get_config
from repro.core.bridge import (B300, TPU_V5E, BridgeModel, Crossing,
                               Direction, StagingKind)
from repro.core.compute import COMPUTE_SPECS, ComputeModel
from repro.core.gateway import TransferGateway
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.overlap import OverlapScheduler
from repro.serving.sampler import SamplingParams
from repro.trace import TraceRecorder, check_tape
from repro.trace import opclasses as oc
from repro.trace.harness import smoke_model


@pytest.fixture(scope="module")
def tiny_model():
    return smoke_model()


def _gw(cc_on=True, workers=1, arena=None):
    return TransferGateway(BridgeModel(TPU_V5E, cc_on=cc_on),
                           cc_aware_defaults(cc_on), pool_workers=workers,
                           arena=arena)


def _defaults(cc_on=True, **overrides):
    return dataclasses.replace(cc_aware_defaults(cc_on, concurrency=4),
                               **overrides)


class TestComputeModel:
    def test_decode_is_memory_bound_at_serving_scale(self):
        cm = ComputeModel(get_config("qwen3p6-27b"), BridgeModel(B300, cc_on=True))
        charge = cm.decode_charge([0.0] * 4)
        assert charge.bound == "memory"
        # weight-read floor: all active params once over HBM
        floor = cm.active_params * cm.bytes_per_param / COMPUTE_SPECS["b300-hgx"].hbm_bw
        assert charge.seconds >= floor
        assert charge.seconds > 1e-3          # ms-scale, like the paper's TPOT

    def test_kv_read_grows_with_prefix_length(self):
        cm = ComputeModel(get_config("qwen3p6-27b"), BridgeModel(B300, cc_on=True))
        assert cm.decode_step_s([4096.0] * 8) > cm.decode_step_s([0.0] * 8)

    def test_prefill_scales_with_cold_tokens_only(self):
        cm = ComputeModel(get_config("qwen3p6-27b"), BridgeModel(B300, cc_on=True))
        assert cm.prefill_s(0) == 0.0
        assert cm.prefill_s(2048) > cm.prefill_s(128) > 0.0

    def test_cc_parity_applies_not_a_bridge_tax(self):
        """L5: device compute under CC pays only the parity factor — a
        memory-bound step slows by 1/hbm_parity, never by a crossing toll."""
        cfg = get_config("qwen3p6-27b")
        on = ComputeModel(cfg, BridgeModel(B300, cc_on=True))
        off = ComputeModel(cfg, BridgeModel(B300, cc_on=False))
        ratio = on.decode_step_s([0.0] * 4) / off.decode_step_s([0.0] * 4)
        assert ratio == pytest.approx(1.0 / B300.hbm_parity, rel=1e-6)


class TestComputeOnClockAndTape:
    def test_charge_compute_advances_clock_and_emits_compute_record(self):
        gw = _gw()
        before = gw.clock.now
        gw.charge_compute(1e-3, op_class=oc.DECODE_COMPUTE)
        assert gw.clock.now == pytest.approx(before + 1e-3)
        assert gw.stats.compute_time_s == pytest.approx(1e-3)
        assert gw.stats.bridge_time_s == 0.0          # compute is not bridge
        rec = gw.records[-1]
        assert rec.kind == "compute" and rec.op_class == oc.DECODE_COMPUTE
        assert rec.direction == "" and rec.nbytes == 0 and rec.charged

    def test_negative_compute_refused(self):
        with pytest.raises(ValueError, match="negative"):
            _gw().charge_compute(-1.0, op_class=oc.DECODE_COMPUTE)

    def test_engine_run_charges_both_prefill_and_decode(self, tiny_model,
                                                        deterministic_seed):
        eng = ServingEngine(tiny_model, max_batch=2, max_len=64,
                            policy=SP.SYNC_DRAIN, cc_on=True,
                            seed=deterministic_seed)
        with TraceRecorder(eng.gateway, label="compute") as rec:
            eng.submit(Request("r0", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=4)))
            eng.run()
        eng.close()
        tape = rec.tape()
        mix = tape.op_class_mix()
        assert mix.get(oc.PREFILL_COMPUTE, 0) == 1
        # the default engine decodes packed (DESIGN.md §10): every decode
        # step's compute lands as a DECODE_PACKED record
        assert mix.get(oc.DECODE_PACKED, 0) == eng.step_count
        assert tape.compute_seconds() > 0.0
        # virtual time covers bridge + compute; stats agree with the tape
        st = eng.stats()
        assert st["compute_time_s"] == pytest.approx(tape.compute_seconds())
        assert st["virtual_time_s"] >= st["bridge_time_s"] + st["compute_time_s"]
        report = check_tape(tape)
        assert report.ok, report.format()
        assert report.checks.get("L1_compute", 0) > 0

    def test_compute_never_overlaps_crossing_on_same_channel(self, tiny_model,
                                                             deterministic_seed):
        """Hand-corrupt a recorded tape: slide a compute interval onto a
        crossing on the same channel — the checker must name the edge."""
        eng = ServingEngine(tiny_model, max_batch=2, max_len=64,
                            policy=SP.SYNC_DRAIN, cc_on=True,
                            seed=deterministic_seed)
        with TraceRecorder(eng.gateway, label="corrupt") as rec:
            eng.submit(Request("r0", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=4)))
            eng.run()
        eng.close()
        tape = rec.tape()
        i = next(i for i, r in enumerate(tape.records) if r.is_compute)
        j = next(j for j, r in enumerate(tape.records)
                 if not r.is_compute and r.channel == tape.records[i].channel)
        victim = tape.records[j]
        records = list(tape.records)
        records[i] = dataclasses.replace(
            records[i], t_start=victim.t_start + 1e-9,
            t_end=victim.t_start + 1e-9 + records[i].duration_s)
        bad = dataclasses.replace(tape, records=records)
        report = check_tape(bad)
        assert not report.ok
        assert any("compute/crossing" in str(v) for v in report.violations)


class TestD2HArenaStaging:
    """Satellite: TransferGateway.d2h routes drain staging through the
    arena — first touch pays the fresh toll exactly once."""

    def test_first_touch_pays_fresh_exactly_once(self):
        gw = _gw(arena=StagingArena(1 << 20))
        dev = np.zeros(64, np.int32)          # numpy stands in for the drain
        for _ in range(4):
            gw.d2h(dev, op_class=oc.DRAIN_D2H)
        stagings = [r.staging for r in gw.records]
        assert stagings == ["fresh", "registered", "registered", "registered"]
        tagged = [r.tags for r in gw.records]
        assert tagged[0] == (oc.ARENA_MISS,)
        assert all(t == (oc.ARENA_HIT,) for t in tagged[1:])
        # the toll delta is the 44x class, paid once
        p = gw.bridge.profile
        fresh, warm = gw.records[0].duration_s, gw.records[1].duration_s
        assert fresh - warm == pytest.approx(
            p.cc_fresh_toll + p.cc_fresh_alloc - p.cc_registered_toll, rel=1e-9)

    def test_drains_share_slabs_with_uploads(self):
        """D2H economics budgeted like H2D: same size class, same slot."""
        gw = _gw(arena=StagingArena(1 << 20))
        gw.h2d(np.zeros(64, np.int32), op_class=oc.PROMPT_H2D)   # pins 256B
        gw.d2h(np.zeros(64, np.int32), op_class=oc.DRAIN_D2H)
        assert gw.records[-1].staging == "registered"
        assert gw.arena.stats.misses == 1 and gw.arena.stats.hits == 1

    def test_without_arena_drains_stay_registered(self):
        """Legacy model unchanged: one persistent output staging buffer."""
        gw = _gw()
        gw.d2h(np.zeros(64, np.int32), op_class=oc.DRAIN_D2H)
        assert gw.records[-1].staging == "registered"


class TestDeadlineRebasedOnCompute:
    def test_compute_charges_age_the_queue_to_its_deadline(self):
        gw = _gw()
        co = CrossingCoalescer(gw, deadline_s=1e-4)
        co.d2h(np.zeros(4, np.int32), op_class="drain")
        gw.charge_compute(5e-4, op_class=oc.DECODE_COMPUTE)   # one forward
        co.poll()                                             # engine contract
        assert co.stats.deadline_flushes == 1
        assert co.pending() == 0

    def test_flush_charge_cannot_strand_the_other_queue(self):
        """A watermark flush moves the clock; the other direction's aged
        queue must meet its deadline off that charge, not wait for luck."""
        gw = _gw()
        co = CrossingCoalescer(gw, threshold_bytes=4096,
                               watermark_bytes=2048, deadline_s=1e-6)
        co.d2h(np.zeros(4, np.int32), op_class="drain")
        for _ in range(4):
            co.h2d(np.zeros(128, np.int32), op_class="prep")  # 2048B: watermark
        assert co.stats.flushes.get("watermark") == 1
        assert co.stats.deadline_flushes >= 1
        assert co.pending(Direction.D2H) == 0

    def test_engine_deadline_fires_under_paper_scale_compute(self, tiny_model,
                                                             deterministic_seed):
        """The acceptance shape: price compute against the paper's 27B
        serving config (executing the smoke model) — every decode step ages
        the queues past the 500us deadline, so the trigger observably fires."""
        bridge = BridgeModel(B300, cc_on=True)
        eng = ServingEngine(
            tiny_model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge,
            defaults=_defaults(coalesce_small_crossings=True),
            compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
            seed=deterministic_seed)
        eng.submit(Request("r0", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=6)))
        eng.run()
        eng.close()
        assert eng.coalescer.stats.deadline_flushes > 0


def _pipelined_restore(gw, *, blocks=96, block_bytes=512 << 10,
                       chunk_bytes=128 << 10):
    """Stage a pipelined restore; returns its completion time."""
    mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                         pipelined_restore=True,
                         restore_chunk_bytes=chunk_bytes)
    for b in range(blocks):
        mgr.host_store[b] = HostBlock(b, block_bytes, 2, None)
    mgr.restore(list(range(blocks)))
    return mgr


class TestRestoreBarrier:
    """Satellite: the PipeLLM correctness edge, both restore shapes."""

    def _engine(self, model, *, overlap, seed=0):
        return ServingEngine(
            model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            cc_on=True, defaults=_defaults(overlap_scheduler=overlap),
            seed=seed)

    def test_step_reading_restored_kv_blocks_to_pipeline_end(self, tiny_model,
                                                             deterministic_seed):
        eng = self._engine(tiny_model, overlap=False, seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        mgr = _pipelined_restore(eng.gateway)
        done_t = mgr.last_restore_done_t
        assert done_t > eng.clock.now          # channels busy past now
        eng.mark_restore("warm", done_t)
        eng.submit(Request("warm", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=2)))
        eng.step()                             # admits + first KV read
        assert eng.clock.now >= done_t
        assert eng.overlap.stats.barrier_waits == 1
        assert eng.overlap.stats.barrier_wait_s > 0
        eng.close()

    def test_step_not_reading_restored_kv_never_pays(self, tiny_model,
                                                     deterministic_seed):
        eng = self._engine(tiny_model, overlap=False, seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        mgr = _pipelined_restore(eng.gateway)
        done_t = mgr.last_restore_done_t
        eng.mark_restore("warm", done_t)       # 'warm' never submitted
        eng.submit(Request("cold", prompt=[1, 2],
                           sampling=SamplingParams(max_new_tokens=1)))
        eng.step()
        assert eng.clock.now < done_t          # no barrier paid
        assert eng.overlap.stats.barrier_waits == 0
        assert eng.overlap.outstanding() == 1  # still pending for 'warm'
        eng.close()

    def test_blocking_restore_makes_the_barrier_a_noop(self, tiny_model,
                                                       deterministic_seed):
        eng = self._engine(tiny_model, overlap=False, seed=deterministic_seed)
        mgr = OffloadManager(eng.gateway, OffloadPolicy.REUSE_AWARE,
                             pipelined_restore=False)
        mgr.host_store[0] = HostBlock(0, 64 << 10, 2, None)
        mgr.restore([0])                       # bulk: blocks the clock now
        done_t = mgr.last_restore_done_t
        assert done_t == pytest.approx(eng.clock.now)
        eng.mark_restore("warm", done_t)
        before = eng.clock.now
        eng.submit(Request("warm", prompt=[1, 2],
                           sampling=SamplingParams(max_new_tokens=1)))
        eng.step()
        assert eng.overlap.stats.barrier_waits == 0
        assert eng.overlap.stats.barrier_noops == 1
        assert eng.clock.now > before          # the step itself, not a wait
        eng.close()

    def test_decode_step_barrier_for_late_restore(self, tiny_model,
                                                  deterministic_seed):
        """A restore marked for an already-running request blocks its next
        decode step, not admission."""
        eng = self._engine(tiny_model, overlap=False, seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        eng.submit(Request("r0", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=4)))
        eng.step()                             # r0 running
        mgr = _pipelined_restore(eng.gateway)
        done_t = mgr.last_restore_done_t
        eng.mark_restore("r0", done_t)
        eng.step()                             # next step reads r0's KV
        assert eng.clock.now >= done_t
        assert eng.overlap.stats.barrier_waits == 1
        eng.close()


class TestOverlapScheduler:
    """Satellite guardrail: overlap-on decode throughput >= overlap-off."""

    def _run(self, model, overlap, seed):
        eng = ServingEngine(
            model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            cc_on=True, defaults=_defaults(overlap_scheduler=overlap),
            seed=seed)
        eng.gateway.pool.prewarm()
        mgr = _pipelined_restore(eng.gateway)
        eng.mark_restore("warm", mgr.last_restore_done_t)
        eng.submit(Request("warm", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=6)))
        eng.submit(Request("cold", prompt=[4, 5, 6],
                           sampling=SamplingParams(max_new_tokens=6)))
        stats = eng.run()
        eng.close()
        return eng, stats

    def test_overlap_on_never_loses(self, tiny_model, deterministic_seed):
        on_eng, on = self._run(tiny_model, True, deterministic_seed)
        off_eng, off = self._run(tiny_model, False, deterministic_seed)
        assert on["total_tokens"] == off["total_tokens"]
        assert on["finished"] == off["finished"]
        # the window was filled with decode work instead of an idle wait
        assert on["virtual_time_s"] <= off["virtual_time_s"] + 1e-12
        tps_on = on["total_tokens"] / on["virtual_time_s"]
        tps_off = off["total_tokens"] / off["virtual_time_s"]
        assert tps_on >= tps_off
        assert on["overlap"]["deferred_admissions"] > 0
        assert off["overlap"]["deferred_admissions"] == 0
        # off pays the barrier as idle wait; on converts (some of) it
        assert (on["overlap"]["barrier_wait_s"]
                <= off["overlap"]["barrier_wait_s"] + 1e-12)

    def test_inert_without_restores(self, tiny_model, deterministic_seed):
        """No restore in flight -> preference changes nothing (what keeps
        golden tapes identical across the CI matrix)."""
        def run(overlap):
            eng = ServingEngine(
                model=tiny_model, max_batch=2, max_len=64,
                policy=SP.SYNC_DRAIN, cc_on=True,
                defaults=_defaults(overlap_scheduler=overlap),
                seed=deterministic_seed)
            eng.submit(Request("r0", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=4)))
            stats = eng.run()
            eng.close()
            return stats
        on, off = run(True), run(False)
        assert on["virtual_time_s"] == pytest.approx(off["virtual_time_s"],
                                                     rel=1e-12)
        assert on["overlap"]["deferred_admissions"] == 0

    def test_deferral_never_livelocks(self, tiny_model, deterministic_seed):
        """A restored request with nothing else to run admits immediately
        (deferral is a preference, not a lock)."""
        eng = ServingEngine(
            tiny_model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            cc_on=True, defaults=_defaults(overlap_scheduler=True),
            seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        mgr = _pipelined_restore(eng.gateway)
        eng.mark_restore("warm", mgr.last_restore_done_t)
        eng.submit(Request("warm", prompt=[1, 2],
                           sampling=SamplingParams(max_new_tokens=2)))
        stats = eng.run()
        eng.close()
        assert stats["finished"] == 1
        assert stats["overlap"]["deferred_admissions"] == 0

    def test_window_reflects_channel_busy_time(self):
        gw = _gw(workers=4)
        gw.pool.prewarm()
        sched = OverlapScheduler(gw.clock, gw.pool)
        assert sched.window_s() == 0.0
        gw.pooled_crossing(Crossing(1 << 20, Direction.H2D,
                                    StagingKind.REGISTERED),
                           op_class=oc.KV_RESTORE_PIPELINED)
        assert sched.window_s() > 0.0


class TestWorkerCoalescerComposition:
    """Satellite/tentpole: the worker thread flushes the coalescer's D2H
    queue instead of being bypassed."""

    def _run(self, model, policy, seed):
        eng = ServingEngine(
            model, max_batch=2, max_len=64, policy=policy, cc_on=True,
            defaults=_defaults(coalesce_small_crossings=True,
                               scheduling=policy),
            seed=seed)
        with TraceRecorder(eng.gateway, policy=policy.value,
                           label=f"compose-{policy.value}") as rec:
            for i in range(2):
                eng.submit(Request(f"r{i}", prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=5)))
            stats = eng.run()
        eng.close()
        return eng, stats, rec.tape()

    def test_worker_takes_fused_drains_off_the_engine_clock(self, tiny_model,
                                                            deterministic_seed):
        eng, stats, tape = self._run(tiny_model, SP.WORKER_DRAIN,
                                     deterministic_seed)
        co = eng.coalescer
        assert co.worker_flush                            # modeled
        assert co.stats.worker_flushes > 0
        assert co.pending() == 0                          # barrier drained all
        fused = [r for r in tape.records if r.op_class == oc.COALESCED_D2H]
        assert fused and all(r.channel >= 0 and not r.charged for r in fused)
        report = check_tape(tape)
        assert report.ok, report.format()

    def test_composition_beats_engine_clock_flushes(self, tiny_model,
                                                    deterministic_seed):
        """Same fused stream, but the drains ride the worker channel: the
        engine clock finishes no later than the sync-coalesced run."""
        worker_eng, worker, _ = self._run(tiny_model, SP.WORKER_DRAIN,
                                          deterministic_seed)
        sync_eng, sync, _ = self._run(tiny_model, SP.SYNC_DRAIN,
                                      deterministic_seed)
        assert worker["total_tokens"] == sync["total_tokens"]
        assert (worker_eng.coalescer.stats.fused_bytes
                == sync_eng.coalescer.stats.fused_bytes)
        assert worker["virtual_time_s"] <= sync["virtual_time_s"] + 1e-12
