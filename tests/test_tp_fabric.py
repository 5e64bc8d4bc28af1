"""Tensor-parallel tenants over fabric P2P (DESIGN.md §12) + the fabric-layer
bugfixes this PR purges: the find_partition health-skip, the zero-context
lease clamp, and the dead fabric_up=False pricing path.
"""

import random

import pytest

try:                                    # property tests upgrade to hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAS_HYPOTHESIS = True
except ImportError:                     # seeded fallback still runs the law
    HAS_HYPOTHESIS = False

from repro.core.bridge import (B300, RTX_PRO_6000, TPU_V5E, BridgeModel,
                               Crossing, Direction, StagingKind)
from repro.core.channels import P2P_CHANNEL, SecureChannelPool, VirtualClock
from repro.core.compute import ComputeModel
from repro.core.fabric import (FabricManager, FabricState, FabricTransport,
                               p2p_bandwidth)
from repro.core.gateway import TransferGateway
from repro.core.policy import OffloadPolicy, cc_aware_defaults
from repro.serving.offload import OffloadManager
from repro.trace import opclasses as oc
from repro.trace.conformance import check_tape
from repro.trace.recorder import TraceRecorder
from repro.trace.replay import ReplaySpec, TraceReplayer


def _gateway(*, profile=TPU_V5E, cc_on=True, workers=2):
    return TransferGateway(BridgeModel(profile, cc_on=cc_on),
                           cc_aware_defaults(cc_on), pool_workers=workers)


# ---------------------------------------------------------------------------
# Satellite 1: find_partition health-skip regression
# ---------------------------------------------------------------------------

class TestHealthGateSkipsToHealthyPartition:
    def test_stale_partition_0_does_not_shadow_healthy_partition_1(self):
        """The bug: find_partition returned the FIRST free partition and
        activate() then refused it — a stale partition 0 made every 4-device
        activation fail even with partition 1 healthy and free."""
        fm = FabricManager(B300)
        fours = [p for p in fm.partitions if p.size == 4]
        fm.mark_stale(fours[0].partition_id)
        tenant = fm.activate("t", 4)
        assert tenant.partition.partition_id == fours[1].partition_id
        assert tenant.fabric_state is FabricState.HEALTHY
        assert fm.check_isolation()["isolated"]

    def test_all_free_partitions_stale_still_raises_health_gate(self):
        fm = FabricManager(B300)
        eight = next(p for p in fm.partitions if p.size == 8)
        fm.mark_stale(eight.partition_id)
        with pytest.raises(RuntimeError, match="health gate"):
            fm.activate("t", 8)

    def test_capacity_exhaustion_still_distinct_from_health_gate(self):
        fm = FabricManager(B300)
        fm.activate("a", 8)
        with pytest.raises(RuntimeError, match="no free"):
            fm.activate("b", 1)

    def test_find_partition_gate_is_opt_in(self):
        """Ungated search still sees stale partitions — activate() needs it
        to tell 'fabric full' apart from 'health gate vetoed'."""
        fm = FabricManager(B300)
        eight = next(p for p in fm.partitions if p.size == 8)
        fm.mark_stale(eight.partition_id)
        assert fm.find_partition(8) is not None
        assert fm.find_partition(8, require_healthy=True) is None


# ---------------------------------------------------------------------------
# Satellite 2: zero-context lease must fail on the budget path
# ---------------------------------------------------------------------------

class TestZeroContextLease:
    def test_replica_spawn_raises_budget_exhausted(self, tiny_model):
        from repro.cluster.budget import BudgetExhausted, ContextLease
        from repro.cluster.replica import Replica
        from repro.cluster.tenant_manager import TenantManager
        tm = TenantManager(TPU_V5E)
        tenant = tm.provision("t0", 1)
        lease = ContextLease(lease_id=0, holder="replica-0", n_contexts=0)
        with pytest.raises(BudgetExhausted, match="0 secure contexts"):
            Replica("replica-0", tiny_model, tenant, lease,
                    BridgeModel(TPU_V5E, cc_on=True))

    def test_one_context_lease_still_spawns(self, tiny_model):
        from repro.cluster.budget import ContextLease
        from repro.cluster.replica import Replica
        from repro.cluster.tenant_manager import TenantManager
        tm = TenantManager(TPU_V5E)
        tenant = tm.provision("t0", 1)
        lease = ContextLease(lease_id=0, holder="replica-0", n_contexts=1)
        r = Replica("replica-0", tiny_model, tenant, lease,
                    BridgeModel(TPU_V5E, cc_on=True))
        assert r.gateway.pool.n_workers == 1
        r.close()


# ---------------------------------------------------------------------------
# Satellite 3: the fabric_up=False path is wired, not dead
# ---------------------------------------------------------------------------

class TestFabricTransport:
    def test_healthy_tenant_rides_full_fabric(self):
        fm = FabricManager(TPU_V5E)
        t = fm.activate("t", 2)
        tr = FabricTransport(TPU_V5E, t)
        assert tr.fabric_up()
        assert tr.bandwidth() == TPU_V5E.fabric_p2p_bw

    def test_stale_tenant_falls_back(self):
        fm = FabricManager(TPU_V5E)
        t = fm.activate("t", 2)
        t.fabric_state = FabricState.STALE
        tr = FabricTransport(TPU_V5E, t)
        assert not tr.fabric_up()
        assert tr.bandwidth() == TPU_V5E.fabric_fallback_bw

    def test_lapsed_attestation_falls_back(self):
        fm = FabricManager(TPU_V5E)
        t = fm.activate("t", 2)
        attested = {"ok": True}
        tr = FabricTransport(TPU_V5E, t, attested=lambda: attested["ok"])
        assert tr.fabric_up()
        attested["ok"] = False            # evidence lapses mid-flight
        assert not tr.fabric_up()

    def test_fabricless_profile_always_falls_back(self):
        tr = FabricTransport(RTX_PRO_6000)
        assert not tr.fabric_up()
        assert tr.bandwidth() == RTX_PRO_6000.fabric_fallback_bw


class TestGatewayP2P:
    def test_p2p_record_shape_and_stats(self):
        gw = _gateway()
        nbytes = 64 << 20
        cost = gw.p2p(nbytes, op_class=oc.P2P_KV_MIGRATE)
        assert cost == pytest.approx(nbytes / TPU_V5E.fabric_p2p_bw)
        rec = gw.records[-1]
        assert rec.kind == "p2p" and rec.op_class == oc.P2P_KV_MIGRATE
        assert rec.direction == Direction.P2P.value
        assert rec.channel == P2P_CHANNEL and rec.staging == ""
        assert rec.charged and oc.FABRIC_FALLBACK not in rec.tags
        assert gw.stats.p2p_crossings == 1
        assert gw.stats.p2p_bytes == nbytes
        assert gw.stats.p2p_fallback_crossings == 0
        # never bridge traffic
        assert gw.stats.bridge_time_s == 0.0
        assert gw.clock.now == pytest.approx(cost)

    def test_down_fabric_prices_fallback_and_tags(self):
        gw = _gateway()
        fm = FabricManager(TPU_V5E)
        t = fm.activate("t", 2)
        t.fabric_state = FabricState.STALE
        gw.fabric = FabricTransport(TPU_V5E, t)
        nbytes = 1 << 20
        cost = gw.p2p(nbytes, op_class=oc.P2P_ALLREDUCE)
        assert cost == pytest.approx(nbytes / TPU_V5E.fabric_fallback_bw)
        assert oc.FABRIC_FALLBACK in gw.records[-1].tags
        assert gw.stats.p2p_fallback_crossings == 1

    def test_negative_bytes_rejected(self):
        with pytest.raises(ValueError, match="negative"):
            _gateway().p2p(-1, op_class=oc.P2P_KV_MIGRATE)

    def test_secure_pool_refuses_p2p_crossings(self):
        """Structural invariant: fabric P2P never rides a secure channel."""
        pool = SecureChannelPool(BridgeModel(TPU_V5E, cc_on=True), 1,
                                 clock=VirtualClock())
        with pytest.raises(ValueError, match="secure copy channels"):
            pool.submit(Crossing(1024, Direction.P2P, StagingKind.REGISTERED))

    def test_p2p_tape_is_conformant_and_excluded_from_bridge_summaries(self):
        import numpy as np
        gw = _gateway()
        with TraceRecorder(gw) as rec:
            gw.h2d(np.zeros(4096, np.uint8), op_class=oc.PREP_BATCHED_H2D)
            gw.p2p(8 << 20, op_class=oc.P2P_SHARD_EXCHANGE)
        tape = rec.tape()
        assert check_tape(tape).ok, check_tape(tape).format()
        assert tape.n_crossings() == 1          # the h2d only
        assert tape.p2p_bytes() == 8 << 20
        assert tape.bridge_bytes() == 4096
        assert tape.p2p_seconds() > 0

    def test_forged_p2p_record_on_a_channel_fails_conformance(self):
        gw = _gateway()
        with TraceRecorder(gw) as rec:
            gw.p2p(1 << 20, op_class=oc.P2P_ALLREDUCE)
        tape = rec.tape()
        import dataclasses
        tape.records[0] = dataclasses.replace(tape.records[0], channel=0)
        report = check_tape(tape)
        assert not report.ok
        assert any("P2P" in v.law for v in report.violations)


# ---------------------------------------------------------------------------
# Satellite 4: fabric lifecycle churn + isolation properties
# ---------------------------------------------------------------------------

class TestFabricChurn:
    def test_activate_deactivate_reactivate_ladder(self):
        fm = FabricManager(B300)
        for size in (1, 2, 4, 8):
            for round_ in range(3):
                tid = f"t{size}-{round_}"
                tenant = fm.activate(tid, size)
                assert fm.check_isolation()["isolated"]
                assert tenant.partition.size == size
                fm.deactivate(tid)
                assert fm.check_isolation()["isolated"]
        # after full churn the fabric is empty and every shape re-findable
        assert not fm.active
        for size in (1, 2, 4, 8):
            assert fm.find_partition(size, require_healthy=True) is not None

    def test_mixed_tenancy_churn_keeps_isolation(self):
        fm = FabricManager(B300)
        fm.activate("a", 4)
        fm.activate("b", 2)
        fm.activate("c", 2)
        assert fm.check_isolation()["isolated"]
        fm.deactivate("b")
        assert fm.check_isolation()["isolated"]
        d = fm.activate("d", 2)                 # freed slot is re-findable
        assert fm.check_isolation()["isolated"]
        assert d.partition.size == 2

    @staticmethod
    def _run_churn_sequence(ops):
        """Property body: under any activate/deactivate sequence, isolation
        holds after every transition and freed partitions come back."""
        fm = FabricManager(B300)
        live: list[str] = []
        counter = 0
        for kind, size in ops:
            if kind == "activate":
                tid = f"t{counter}"
                counter += 1
                try:
                    fm.activate(tid, size)
                    live.append(tid)
                except RuntimeError:
                    pass                        # fabric full for that shape
            elif live:
                fm.deactivate(live.pop(0))
            report = fm.check_isolation()
            assert report["isolated"], report
            owned = [d for t in fm.active.values()
                     for d in t.partition.device_ids]
            assert len(owned) == len(set(owned))
        for tid in live:
            fm.deactivate(tid)
        assert fm.find_partition(8, require_healthy=True) is not None

    if HAS_HYPOTHESIS:
        @given(ops=st.lists(
            st.tuples(st.sampled_from(["activate", "deactivate"]),
                      st.sampled_from([1, 2, 4, 8])),
            min_size=1, max_size=40))
        @settings(max_examples=60, deadline=None)
        def test_no_device_ever_owned_by_two_active_tenants(self, ops):
            self._run_churn_sequence(ops)
    else:
        def test_no_device_ever_owned_by_two_active_tenants(self):
            rng = random.Random(0xFAB)
            for _ in range(60):
                ops = [(rng.choice(["activate", "deactivate"]),
                        rng.choice([1, 2, 4, 8]))
                       for _ in range(rng.randint(1, 40))]
                self._run_churn_sequence(ops)


# ---------------------------------------------------------------------------
# Tentpole: TP pricing model
# ---------------------------------------------------------------------------

class TestTPComputeModel:
    def test_tp_degree_must_be_positive(self):
        from repro.configs.base import all_configs, smoke_config
        cfg = smoke_config(all_configs()["olmo-1b"])
        with pytest.raises(ValueError, match="tp_degree"):
            ComputeModel(cfg, BridgeModel(TPU_V5E, cc_on=True), tp_degree=0)

    def test_allreduce_zero_for_tp1_and_empty_batch(self):
        from repro.configs.base import all_configs, smoke_config
        cfg = smoke_config(all_configs()["olmo-1b"])
        bridge = BridgeModel(TPU_V5E, cc_on=True)
        assert ComputeModel(cfg, bridge).allreduce_bytes(4) == 0
        assert ComputeModel(cfg, bridge, tp_degree=4).allreduce_bytes(0) == 0

    def test_ring_allreduce_bytes_formula(self):
        from repro.configs.base import all_configs, smoke_config
        cfg = smoke_config(all_configs()["olmo-1b"])
        cm = ComputeModel(cfg, BridgeModel(TPU_V5E, cc_on=True), tp_degree=4)
        batch = 3
        payload = 2 * cfg.n_layers * batch * cfg.d_model * cm.bytes_per_param
        assert cm.allreduce_bytes(batch) == int(2 * 3 / 4 * payload)
        assert cm.allreduce_seconds(batch, TPU_V5E.fabric_p2p_bw) == \
            pytest.approx(cm.allreduce_bytes(batch) / TPU_V5E.fabric_p2p_bw)

    def test_per_device_step_divides_by_tp(self):
        from repro.configs.base import get_config
        cfg = get_config("nemotron-4-340b")
        bridge = BridgeModel(B300, cc_on=True)
        one = ComputeModel(cfg, bridge).decode_charge([512.0] * 8)
        four = ComputeModel(cfg, bridge, tp_degree=4).decode_charge(
            [512.0] * 8)
        assert four.seconds == pytest.approx(one.seconds / 4)
        assert four.flops == pytest.approx(one.flops / 4)

    def test_340b_tp4_step_beats_tp1_even_with_allreduce(self):
        """The CI guardrail's model-level core: on nemotron-4-340b the TP=4
        per-device step (compute + its fabric allreduce) is faster than the
        TP=1 step — the allreduce is small against the weight stream."""
        from repro.configs.base import get_config
        cfg = get_config("nemotron-4-340b")
        bridge = BridgeModel(B300, cc_on=True)
        batch = 8
        tp1 = ComputeModel(cfg, bridge).decode_charge([512.0] * batch)
        cm4 = ComputeModel(cfg, bridge, tp_degree=4)
        tp4 = (cm4.decode_charge([512.0] * batch).seconds
               + cm4.allreduce_seconds(batch, B300.fabric_p2p_bw))
        assert tp4 < tp1.seconds


# ---------------------------------------------------------------------------
# Tentpole: loader shard exchange + KV migration ride P2P, never the bridge
# ---------------------------------------------------------------------------

class TestShardExchangeAndMigration:
    def test_loader_tp_load_adds_p2p_not_bridge_bytes(self, tmp_path):
        import numpy as np
        from repro.loader.pooled_loader import LoaderVariant, PooledLoader
        from repro.loader.sharded_weights import (ShardedCheckpoint,
                                                  save_sharded)
        tensors = {f"w{i}": np.zeros((64, 64), np.float32) for i in range(4)}
        save_sharded(str(tmp_path), tensors, n_shards=2)
        ckpt = ShardedCheckpoint(str(tmp_path))

        def load_with(tp):
            gw = _gateway(workers=2)
            loader = PooledLoader(gw.bridge, n_workers=2, gateway=gw,
                                  clock=gw.clock)
            with TraceRecorder(gw) as rec:
                loader.load(ckpt, LoaderVariant.PREWARMED, tp_degree=tp)
            return rec.tape(), gw

        tape1, _ = load_with(1)
        tape4, gw4 = load_with(4)
        # CVM ingress (bridge) bytes identical; only fabric bytes grow
        assert tape4.bridge_bytes() == tape1.bridge_bytes()
        assert tape1.p2p_bytes() == 0
        total = ckpt.total_bytes()
        assert tape4.p2p_bytes() == int(total * 3 / 4)
        assert gw4.stats.p2p_crossings == 1
        mix = tape4.op_class_mix()
        assert mix.get(oc.P2P_SHARD_EXCHANGE) == 1
        assert check_tape(tape4).ok

    def test_loader_rejects_nonpositive_tp(self, tmp_path):
        from repro.loader.pooled_loader import LoaderVariant, PooledLoader
        with pytest.raises(ValueError, match="tp_degree"):
            PooledLoader(BridgeModel(TPU_V5E, cc_on=True)).load(
                None, LoaderVariant.BASELINE, tp_degree=0)

    def test_kv_migrate_moves_blocks_over_fabric_only(self):
        gw = _gateway()
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             store_threshold=1, block_bytes=4096)
        hashes = [hash(("p", i)) for i in range(3)]
        for h in hashes:
            mgr.observe(h)
            mgr.evict(h, payload_bytes=4096)
        with TraceRecorder(gw) as rec:
            moved, nbytes = mgr.migrate(hashes)
        assert (moved, nbytes) == (3, 3 * 4096)
        assert mgr.stats.migrated_blocks == 3
        assert mgr.stats.migrated_bytes == 3 * 4096
        tape = rec.tape()
        assert tape.n_crossings() == 0          # zero bridge crossings
        assert tape.p2p_bytes() == 3 * 4096
        assert tape.op_class_mix().get(oc.P2P_KV_MIGRATE) == 1

    def test_migrate_unknown_hashes_is_free(self):
        gw = _gateway()
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             store_threshold=1, block_bytes=4096)
        assert mgr.migrate([hash("nope")]) == (0, 0)
        assert gw.stats.p2p_crossings == 0


# ---------------------------------------------------------------------------
# Tentpole: TP replica groups end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs.base import all_configs, smoke_config
    from repro.models.model import Model
    return Model(smoke_config(all_configs()["olmo-1b"]))


def _serve(model, tp, *, seed=7):
    from repro.cluster import RoutingPolicy, build_cluster
    from repro.cluster.replica import ReplicaConfig
    from repro.serving.engine import Request
    from repro.serving.sampler import SamplingParams
    cluster = build_cluster(
        model, cc_on=True, n_replicas=1, partition_size=4,
        replica_cfg=ReplicaConfig(tp_degree=tp),
        routing=RoutingPolicy.LEAST_LOADED, seed=seed)
    for i in range(3):
        cluster.submit(Request(f"r{i}", prompt=list(range(1, 17)) + [30 + i],
                               sampling=SamplingParams(max_new_tokens=4)))
    cluster.run()
    replica = cluster.replicas[0]
    tokens = {r.request_id: list(r.output_tokens)
              for r in replica.engine.finished}
    tape = replica.tape()
    stats = replica.stats()
    cluster.close()
    return tokens, tape, stats


class TestTPReplicaGroups:
    def test_tp4_tokens_byte_identical_to_tp1(self, tiny_model):
        """TP is a pricing change, not an execution change: the golden
        workload's token streams match byte for byte across degrees."""
        tok1, tape1, _ = _serve(tiny_model, 1)
        tok4, tape4, st4 = _serve(tiny_model, 4)
        assert tok1 == tok4 and tok1
        # TP=1 emits zero p2p; TP=4 rides the fabric for its allreduces
        assert tape1.p2p_bytes() == 0
        assert tape4.p2p_bytes() > 0
        assert tape4.op_class_mix().get(oc.P2P_ALLREDUCE, 0) > 0
        assert st4["tp_degree"] == 4
        assert st4["p2p_bytes"] == tape4.p2p_bytes()
        # bridge traffic does not grow with the degree (only CVM ingress
        # pays the toll) and the tape stays law-abiding
        assert tape4.bridge_bytes() == tape1.bridge_bytes()
        assert check_tape(tape4).ok, check_tape(tape4).format()

    def test_allreduce_priced_at_fabric_rate(self, tiny_model):
        _, tape, _ = _serve(tiny_model, 4)
        p2p = [r for r in tape.records if r.is_p2p]
        assert p2p
        for r in p2p:
            assert r.duration_s == pytest.approx(
                r.nbytes / TPU_V5E.fabric_p2p_bw)
            assert oc.FABRIC_FALLBACK not in r.tags

    def test_stall_attribution_closes_with_p2p(self, tiny_model):
        from repro.obs.stalls import CAUSE_P2P, attribute_stalls
        _, tape, _ = _serve(tiny_model, 4)
        report = attribute_stalls(tape)
        assert report.closure >= 0.99
        assert report.share(CAUSE_P2P) > 0

    def test_tp_must_fit_partition(self, tiny_model):
        from repro.cluster import build_cluster
        from repro.cluster.replica import ReplicaConfig
        with pytest.raises(ValueError, match="does not fit partition_size"):
            build_cluster(tiny_model, n_replicas=1, partition_size=2,
                          replica_cfg=ReplicaConfig(tp_degree=4))

    def test_replica_validates_tp_against_tenant(self, tiny_model):
        from repro.cluster.budget import ContextLease
        from repro.cluster.replica import Replica, ReplicaConfig
        from repro.cluster.tenant_manager import TenantManager
        tenant = TenantManager(TPU_V5E).provision("t0", 2)
        lease = ContextLease(lease_id=0, holder="replica-0", n_contexts=2)
        with pytest.raises(ValueError, match="does not fit"):
            Replica("replica-0", tiny_model, tenant, lease,
                    BridgeModel(TPU_V5E, cc_on=True),
                    ReplicaConfig(tp_degree=4))

    def test_unattested_replica_reprices_p2p_at_fallback(self, tiny_model):
        """Satellite 3 end to end: drop the replica's attestation standing
        and the SAME movement class reprices at the TCP fallback, tagged."""
        from repro.cluster import RoutingPolicy, build_cluster
        from repro.cluster.replica import ReplicaConfig
        from repro.serving.engine import Request
        from repro.serving.sampler import SamplingParams
        cluster = build_cluster(
            tiny_model, cc_on=True, n_replicas=1, partition_size=4,
            replica_cfg=ReplicaConfig(tp_degree=4),
            routing=RoutingPolicy.LEAST_LOADED, seed=7)
        replica = cluster.replicas[0]
        cluster.submit(Request("r0", prompt=list(range(1, 17)),
                               sampling=SamplingParams(max_new_tokens=3)))
        replica.attested = False        # evidence lapses before decoding
        while replica.pending():
            replica.tick()
        tape = replica.tape()
        p2p = [r for r in tape.records if r.is_p2p]
        assert p2p
        for r in p2p:
            assert oc.FABRIC_FALLBACK in r.tags
            assert r.duration_s == pytest.approx(
                r.nbytes / TPU_V5E.fabric_fallback_bw)
        assert replica.gateway.stats.p2p_fallback_crossings == len(p2p)
        assert check_tape(tape).ok
        cluster.close()


# ---------------------------------------------------------------------------
# Replay: the fabric_up lever
# ---------------------------------------------------------------------------

class TestReplayFabricLever:
    def _tape_with_p2p(self, *, down=False):
        gw = _gateway()
        if down:
            fm = FabricManager(TPU_V5E)
            t = fm.activate("t", 2)
            t.fabric_state = FabricState.STALE
            gw.fabric = FabricTransport(TPU_V5E, t)
        with TraceRecorder(gw) as rec:
            gw.p2p(32 << 20, op_class=oc.P2P_ALLREDUCE)
        return rec.tape()

    def test_as_recorded_replay_respects_fallback_tag(self):
        nbytes = 32 << 20
        healthy = TraceReplayer(self._tape_with_p2p()).reprice(ReplaySpec())
        lapsed = TraceReplayer(
            self._tape_with_p2p(down=True)).reprice(ReplaySpec())
        assert healthy.total_replayed_s == pytest.approx(
            nbytes / TPU_V5E.fabric_p2p_bw)
        assert lapsed.total_replayed_s == pytest.approx(
            nbytes / TPU_V5E.fabric_fallback_bw)

    def test_forcing_fabric_down_reprices_same_bytes(self):
        tape = self._tape_with_p2p()
        up = TraceReplayer(tape).reprice(ReplaySpec(fabric_up=True))
        down = TraceReplayer(tape).reprice(ReplaySpec(fabric_up=False))
        assert down.total_replayed_s == pytest.approx(
            up.total_replayed_s * TPU_V5E.fabric_p2p_bw
            / TPU_V5E.fabric_fallback_bw)

    def test_cross_profile_replay_uses_target_fabric(self):
        tape = self._tape_with_p2p()
        on_b300 = TraceReplayer(tape).reprice(ReplaySpec(profile="b300-hgx"))
        assert on_b300.total_replayed_s == pytest.approx(
            (32 << 20) / B300.fabric_p2p_bw)
        # a fabricless profile prices P2P at its TCP fallback, never crashes
        no_fabric = TraceReplayer(tape).reprice(
            ReplaySpec(profile="rtx-pro-6000"))
        assert no_fabric.total_replayed_s == pytest.approx(
            (32 << 20) / RTX_PRO_6000.fabric_fallback_bw)

    def test_p2p_bandwidth_fallback_constant(self):
        assert p2p_bandwidth(TPU_V5E, fabric_up=False) == \
            TPU_V5E.fabric_fallback_bw
