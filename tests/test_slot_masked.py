"""Slot-masked decode (ISSUE 5, DESIGN.md §8) + the PR's satellite surfaces.

The laws pinned here:

  * ready-mask: a decode step steps exactly the slots whose restore
    pipelines have landed; deferred slots stay resident and rejoin with the
    SAME output tokens a run with no restore produces (greedy decode — the
    mask is an accounting/consumption boundary, not a numerics change);
  * masked steps charge compute for the ready slots only (DECODE_PACKED
    records with deferred tags), never the full batch;
  * a non-restore workload never masks, and its StepTrace does not depend
    on the overlap preference;
  * when every slot is deferred, the nearest pipeline's barrier is paid —
    the batch always makes progress (law over preference);
  * PinnedBudget: arena bytes are a host-wide resource; over-subscription
    is rejected at replica spawn, never shrunk silently;
  * compute tape records carry roofline boundness and replay re-prices at
    the matching parity factor (hbm_parity for memory-bound steps);
  * overlap-aware routing: with the flag on, load ties break toward the
    replica with the higher barrier-noop share.
"""

import dataclasses

import numpy as np
import pytest

from repro.cluster import (BudgetExhausted, PinnedBudget, build_cluster)
from repro.cluster.replica import ReplicaConfig
from repro.cluster.router import ClusterRouter, RoutingPolicy
from repro.configs.base import get_config
from repro.core.bridge import B300, TPU_V5E, BridgeModel
from repro.core.compute import ComputeModel
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.overlap import OverlapScheduler
from repro.serving.sampler import SamplingParams
from repro.trace import ReplaySpec, TraceRecorder, TraceReplayer, check_tape
from repro.trace import opclasses as oc
from repro.trace.harness import smoke_model


@pytest.fixture(scope="module")
def tiny_model():
    return smoke_model()


def _defaults(**overrides):
    return dataclasses.replace(cc_aware_defaults(True, concurrency=4),
                               **overrides)


def _stage_late_restore(engine, key, *, blocks=96, block_bytes=128 << 10,
                        chunk_bytes=8 << 10):
    """Kick off a pipelined restore for `key` through the offload layer's
    completion callback (the per-slot notification path the replica uses)."""
    mgr = OffloadManager(engine.gateway, OffloadPolicy.REUSE_AWARE,
                         pipelined_restore=True,
                         restore_chunk_bytes=chunk_bytes)
    for b in range(blocks):
        mgr.host_store[b] = HostBlock(b, block_bytes, 2, None)
    mgr.on_restore_done.append(engine.mark_restore)
    mgr.restore(list(range(blocks)), key=key)
    return mgr


def _run_restore_under_decode(model, *, restore=True, seed=0, record=False):
    """The tentpole scenario: 4 slots decoding, one slot's restore pipeline
    starts draining mid-run (with ``restore``).  r0 has a short tail so
    masking can hide the window entirely; the others keep the batch busy."""
    bridge = BridgeModel(B300, cc_on=True)
    engine = ServingEngine(
        model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN, bridge=bridge,
        defaults=_defaults(),
        compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
        seed=seed)
    engine.gateway.pool.prewarm()
    recorder = (TraceRecorder(engine.gateway, label="slot-masked").attach()
                if record else None)
    engine.submit(Request("r0", prompt=[1, 2, 3],
                          sampling=SamplingParams(max_new_tokens=4)))
    for i in range(1, 4):
        engine.submit(Request(f"r{i}", prompt=[1, 2, 3],
                              sampling=SamplingParams(max_new_tokens=16)))
    engine.step()                      # everyone running
    if restore:
        _stage_late_restore(engine, "r0")  # r0's pipeline starts draining
    stats = engine.run()
    if recorder is not None:
        recorder.detach()
    engine.close()
    tokens = {r.request_id: list(r.output_tokens) for r in engine.finished}
    tape = recorder.tape() if recorder is not None else None
    return engine, stats, tokens, tape


class TestReadyMask:
    def _sched(self, workers=4):
        from repro.core.gateway import TransferGateway
        gw = TransferGateway(BridgeModel(TPU_V5E, cc_on=True),
                             cc_aware_defaults(True), pool_workers=workers)
        return gw, OverlapScheduler(gw.clock, gw.pool)

    def test_no_pending_means_all_ready(self):
        _, sched = self._sched()
        assert sched.ready_mask({0: "a", 1: "b"}) == {0: True, 1: True}

    def test_draining_slot_not_ready_until_pipeline_lands(self):
        gw, sched = self._sched()
        sched.note_restore("a", gw.clock.now + 1.0)
        assert sched.ready_mask({0: "a", 1: "b"}) == {0: False, 1: True}
        gw.clock.advance(1.0)
        assert sched.ready_mask({0: "a", 1: "b"}) == {0: True, 1: True}
        # readiness does not resolve the pending entry; the barrier does
        assert sched.outstanding() == 1
        assert sched.restore_barrier("a") == 0.0
        assert sched.stats.barrier_noops == 1

    def test_pending_done_t_and_deferral_stat(self):
        gw, sched = self._sched()
        assert sched.pending_done_t("a") is None
        sched.note_restore("a", 2.5)
        assert sched.pending_done_t("a") == 2.5
        sched.record_slot_deferral("a")
        sched.record_slot_deferral("a")
        assert sched.stats.deferred_slots == 2
        assert sched.stats_dict()["deferred_slots"] == 2


class TestSlotMaskedDecode:
    def test_deferred_slot_rejoins_with_unmasked_tokens(self, tiny_model,
                                                        deterministic_seed):
        """Rejoin correctness: masking changes timing, never tokens."""
        _, on, tok_on, _ = _run_restore_under_decode(
            tiny_model, seed=deterministic_seed)
        _, off, tok_off, _ = _run_restore_under_decode(
            tiny_model, restore=False, seed=deterministic_seed)
        assert tok_on == tok_off
        assert on["finished"] == off["finished"] == 4
        assert on["overlap"]["deferred_slots"] > 0
        assert off["overlap"]["deferred_slots"] == 0

    def test_step_trace_counts_deferrals_and_matches_stats(self, tiny_model,
                                                           deterministic_seed):
        eng, stats, _, _ = _run_restore_under_decode(
            tiny_model, seed=deterministic_seed)
        per_step = [t.deferred for t in eng.trace]
        assert sum(per_step) == stats["overlap"]["deferred_slots"] > 0
        # a masked step steps fewer slots; prep bytes shrink with the mask
        masked_steps = [t for t in eng.trace if t.deferred]
        full_steps = [t for t in eng.trace if not t.deferred and t.active == 4]
        assert masked_steps and full_steps
        assert all(t.active + t.deferred <= 4 for t in eng.trace)
        assert masked_steps[0].prep_bytes < full_steps[0].prep_bytes
        assert masked_steps[0].drain_bytes < full_steps[0].drain_bytes

    def test_masked_compute_records_on_tape(self, tiny_model,
                                            deterministic_seed):
        """Masked steps are tape-visible: DECODE_PACKED compute records
        carrying one DEFERRED tag per deferred slot, and a conformant
        stream."""
        eng, _, _, tape = _run_restore_under_decode(
            tiny_model, seed=deterministic_seed, record=True)
        mix = tape.op_class_mix()
        n_masked = sum(1 for t in eng.trace if t.deferred)
        assert n_masked > 0
        assert mix.get(oc.DECODE_PACKED, 0) == eng.step_count
        assert oc.DECODE_MASKED not in mix and oc.DECODE_COMPUTE not in mix
        masked_recs = [r for r in tape.records
                       if r.op_class == oc.DECODE_PACKED
                       and oc.DEFERRED in r.tags]
        assert len(masked_recs) == n_masked
        # one DEFERRED per deferred slot-step: tag counts read as
        # deferred slot-steps
        tags = tape.tag_counts()
        assert tags.get(oc.DEFERRED, 0) == sum(t.deferred for t in eng.trace)
        assert tags.get(oc.PACKED, 0) == eng.step_count
        assert all(r.is_compute and r.bound in ("compute", "memory")
                   for r in masked_recs)
        report = check_tape(tape)
        assert report.ok, report.format()

    def test_masked_step_charges_exactly_the_masked_batch(self, tiny_model,
                                                          deterministic_seed):
        """The clock sees the true smaller charge: one slot of two deferred
        means the step prices exactly the ready slot's KV — strictly below
        the full-batch price at the same depth."""
        bridge = BridgeModel(B300, cc_on=True)
        eng = ServingEngine(
            tiny_model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge, defaults=_defaults(),
            compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
            seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        for rid in ("r0", "r1"):
            eng.submit(Request(rid, prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=8)))
        eng.step()                         # both running
        _stage_late_restore(eng, "r0")     # r0's pipeline drains
        kv = float(next(r.index for r in eng.active.values()
                        if r.request_id == "r1"))
        with TraceRecorder(eng.gateway, label="one-step") as rec:
            eng.step()                     # masks r0, steps r1 alone
        masked = [r for r in rec.tape().records
                  if r.op_class == oc.DECODE_PACKED]
        eng.close()
        assert len(masked) == 1 and masked[0].tags.count(oc.DEFERRED) == 1
        expected = eng.compute.decode_charge([kv])
        assert masked[0].duration_s == pytest.approx(expected.seconds,
                                                     rel=1e-12)
        assert masked[0].bound == expected.bound
        assert masked[0].duration_s < eng.compute.decode_charge(
            [kv, kv]).seconds

    def test_all_slots_deferred_pays_nearest_barrier(self, tiny_model,
                                                     deterministic_seed):
        """Law over preference: a batch with no ready slot blocks to the
        nearest pipeline end instead of spinning."""
        eng = ServingEngine(
            tiny_model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            cc_on=True, defaults=_defaults(overlap_scheduler=False),
            seed=deterministic_seed)
        eng.gateway.pool.prewarm()
        eng.submit(Request("solo", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=3)))
        eng.step()                        # solo running
        mgr = _stage_late_restore(eng, "solo")
        done_t = mgr.last_restore_done_t
        assert done_t > eng.clock.now
        stats = eng.run()
        eng.close()
        assert stats["finished"] == 1
        assert eng.clock.now >= done_t
        assert stats["overlap"]["barrier_waits"] == 1

    def test_non_restore_workload_identical_step_trace(self, tiny_model,
                                                       deterministic_seed):
        """Guardrail: with no restores in flight nothing is masked, and the
        overlap preference changes nothing — the StepTrace streams (and
        stats) are equal field for field."""
        def run(prefer):
            eng = ServingEngine(
                tiny_model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
                cc_on=True, defaults=_defaults(overlap_scheduler=prefer),
                seed=deterministic_seed)
            for i in range(6):
                eng.submit(Request(f"r{i}", prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=6)))
            stats = eng.run()
            eng.close()
            return eng.trace, stats
        trace_on, on = run(True)
        trace_off, off = run(False)
        assert trace_on == trace_off
        assert all(t.deferred == 0 and t.packed == t.active
                   for t in trace_on)
        assert on["virtual_time_s"] == off["virtual_time_s"]
        assert on["overlap"]["deferred_slots"] == 0

    def test_masked_pricing_path(self):
        """decode_charge prices exactly the ready slots: weight reads are
        batch-independent, KV traffic sums the ready prefixes only."""
        cm = ComputeModel(get_config("qwen3p6-27b"),
                          BridgeModel(B300, cc_on=True))
        full = cm.decode_charge([1024.0] * 4)
        masked = cm.decode_charge([1024.0] * 3)
        assert masked.seconds < full.seconds
        assert full.flops - masked.flops == 2.0 * cm.active_params
        assert full.hbm_bytes - masked.hbm_bytes == pytest.approx(
            1024.0 * cm.kv_bytes_per_token(), rel=1e-9)
        assert masked.bound in ("compute", "memory")


class TestPinnedBudget:
    def test_full_grant_or_rejection(self):
        b = PinnedBudget(100)
        lease = b.acquire("r0", 60)
        assert lease.nbytes == 60 and b.available() == 40
        with pytest.raises(BudgetExhausted, match="over-subscribed"):
            b.acquire("r1", 41)           # no partial grants
        b.acquire("r1", 40)
        assert b.available() == 0
        b.release("r0")
        assert b.available() == 60

    def test_unconstrained_and_zero_lease(self):
        b = PinnedBudget()
        assert b.acquire("r0", 1 << 40).nbytes == 1 << 40
        assert PinnedBudget(10).acquire("r0", 0).nbytes == 0

    def test_double_lease_and_negative_rejected(self):
        b = PinnedBudget(10)
        b.acquire("r0", 5)
        with pytest.raises(ValueError, match="already holds"):
            b.acquire("r0", 1)
        with pytest.raises(ValueError, match="negative"):
            b.acquire("r1", -1)

    def test_max_replicas_planning(self):
        b = PinnedBudget(100 << 20)
        assert b.max_replicas(32 << 20) == 3
        b.acquire("r0", 32 << 20)
        assert b.max_replicas(32 << 20) == 2

    def test_cluster_spawn_rejects_oversubscription(self, tiny_model,
                                                    deterministic_seed):
        cfg = ReplicaConfig(max_batch=2, max_len=48, n_pages=16,
                            staging_arena_bytes=32 << 20)
        with pytest.raises(BudgetExhausted, match="over-subscribed"):
            build_cluster(tiny_model, n_replicas=2, replica_cfg=cfg,
                          host_pinned_bytes=48 << 20,   # fits one, not two
                          seed=deterministic_seed)

    def test_cluster_spawn_within_budget_leases_and_releases(
            self, tiny_model, deterministic_seed):
        cfg = ReplicaConfig(max_batch=2, max_len=48, n_pages=16,
                            staging_arena_bytes=16 << 20)
        cluster = build_cluster(tiny_model, n_replicas=2, replica_cfg=cfg,
                                host_pinned_bytes=64 << 20,
                                seed=deterministic_seed)
        try:
            # each lease covers the FULL pinned footprint: 16 MiB arena +
            # 8 granted contexts x 1 MiB channel slot = 24 MiB per replica
            assert cluster.pinned_budget.allocated() == 48 << 20
            assert all(r.pinned_lease is not None
                       and r.pinned_lease.nbytes
                       == r.cfg.pinned_bytes(r.lease.n_contexts) == 24 << 20
                       for r in cluster.replicas)
        finally:
            cluster.close()
        assert cluster.pinned_budget.allocated() == 0


class TestBoundRepricing:
    def _b300_tape(self, model, seed):
        bridge = BridgeModel(B300, cc_on=True)
        eng = ServingEngine(
            model, max_batch=2, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge, defaults=_defaults(),
            compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
            seed=seed)
        with TraceRecorder(eng.gateway, label="bound") as rec:
            eng.submit(Request("r0", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=4)))
            eng.run()
        eng.close()
        return rec.tape()

    def test_records_carry_boundness(self, tiny_model, deterministic_seed):
        tape = self._b300_tape(tiny_model, deterministic_seed)
        compute = [r for r in tape.records if r.is_compute]
        assert compute and all(r.bound in ("compute", "memory")
                               for r in compute)
        # serving-scale decode is weight-read memory-bound (DESIGN.md §7)
        assert any(r.bound == "memory" for r in compute
                   if r.op_class == oc.DECODE_PACKED)
        crossings = [r for r in tape.records if not r.is_compute]
        assert all(r.bound == "" for r in crossings)

    def test_replay_reprices_at_matching_parity(self, tiny_model,
                                                deterministic_seed):
        """A memory-bound compute second recorded CC-on re-prices CC-off by
        hbm_parity (B300: 0.912 — a real tax), not compute_parity (0.998)."""
        tape = self._b300_tape(tiny_model, deterministic_seed)
        res = TraceReplayer(tape).reprice(ReplaySpec(cc_on=False))
        rec_mem = sum(r.duration_s for r in tape.records
                      if r.bound == "memory")
        rec_cmp = sum(r.duration_s for r in tape.records
                      if r.bound == "compute")
        assert rec_mem > 0
        # replayed compute seconds = memory-bound at hbm_parity +
        # compute-bound at compute_parity
        expect = rec_mem * B300.hbm_parity + rec_cmp * B300.compute_parity
        got = sum(r.calls * r.cc_off_avg_us * 1e-6 for r in res.rows
                  if r.op_class in (oc.DECODE_PACKED, oc.PREFILL_COMPUTE))
        assert got == pytest.approx(expect, rel=1e-9)
        # the old conservative scaling would have been measurably different
        assert abs(got - (rec_mem + rec_cmp) * B300.compute_parity) > 1e-6

    def test_preboundness_tapes_fall_back_conservatively(self, tiny_model,
                                                         deterministic_seed):
        """A tape without `bound` (older recorder) re-prices compute at
        compute_parity — exactly the pre-satellite behavior."""
        tape = self._b300_tape(tiny_model, deterministic_seed)
        stripped = dataclasses.replace(
            tape, records=[dataclasses.replace(r, bound="")
                           for r in tape.records])
        res = TraceReplayer(stripped).reprice(ReplaySpec(cc_on=False))
        rec_compute = stripped.compute_seconds()
        got = sum(r.calls * r.cc_off_avg_us * 1e-6 for r in res.rows
                  if r.op_class in (oc.DECODE_PACKED, oc.PREFILL_COMPUTE))
        assert got == pytest.approx(rec_compute * B300.compute_parity,
                                    rel=1e-9)


class _StubOverlapReplica:
    """Just enough surface for overlap-aware routing decisions."""

    def __init__(self, replica_id, load, noop_share):
        self.replica_id = replica_id
        self.cfg = ReplicaConfig()
        self._load = load
        self._share = noop_share
        self.submitted = []

    def kv_inventory(self):
        return set()

    def load_score(self):
        return self._load

    def overlap_noop_share(self):
        return self._share

    def pending(self):
        return 0

    def submit(self, req, prefix_hashes=None):
        self.submitted.append(req)
        return True


def _req(rid):
    return Request(rid, prompt=list(range(16)),
                   sampling=SamplingParams(max_new_tokens=2))


class TestOverlapAwareRouting:
    def test_flag_on_prefers_high_noop_share_on_load_tie(self):
        idle_wait = _StubOverlapReplica("idle-wait", 1.0, 0.1)
        filled = _StubOverlapReplica("filled", 1.0, 0.9)
        router = ClusterRouter([idle_wait, filled],
                               routing=RoutingPolicy.LEAST_LOADED,
                               prefer_overlap_filled=True)
        picks = [router.submit(_req(f"r{i}")).replica_id for i in range(3)]
        assert picks == ["filled", "filled", "filled"]

    def test_flag_off_keeps_round_robin(self):
        a = _StubOverlapReplica("a", 1.0, 0.1)
        b = _StubOverlapReplica("b", 1.0, 0.9)
        router = ClusterRouter([a, b], routing=RoutingPolicy.LEAST_LOADED)
        picks = [router.submit(_req(f"r{i}")).replica_id for i in range(4)]
        assert picks == ["a", "b", "a", "b"]

    def test_load_still_dominates_share(self):
        """The preference breaks ties; it never routes to a busier replica."""
        busy_filled = _StubOverlapReplica("busy", 5.0, 1.0)
        idle_cold = _StubOverlapReplica("idle", 1.0, 0.0)
        router = ClusterRouter([busy_filled, idle_cold],
                               routing=RoutingPolicy.LEAST_LOADED,
                               prefer_overlap_filled=True)
        assert router.submit(_req("r0")).replica_id == "idle"

    def test_share_ties_fall_back_round_robin(self):
        a = _StubOverlapReplica("a", 1.0, 0.5)
        b = _StubOverlapReplica("b", 1.0, 0.5)
        router = ClusterRouter([a, b], routing=RoutingPolicy.LEAST_LOADED,
                               prefer_overlap_filled=True)
        picks = [router.submit(_req(f"r{i}")).replica_id for i in range(4)]
        assert picks == ["a", "b", "a", "b"]
