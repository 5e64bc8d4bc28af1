"""Failure-path hardening around the cluster (ISSUE 8 satellites): restore
callback isolation, an engine shutdown with no thread to strand, lease/page
leak audits,
attestation-denied routing, autoscaler spawn backoff, and drain-and-re-route
failover end to end."""

import threading

import pytest

from repro.cluster import (Autoscaler, AutoscalerConfig, BudgetExhausted,
                           PinnedBudget, ReplicaConfig, RoutingPolicy,
                           SecureContextBudget, build_cluster)
from repro.cluster.replica import Replica
from repro.cluster.router import ClusterRouter
from repro.cluster.tenant_manager import TenantManager
from repro.core.bridge import TPU_V5E, BridgeModel
from repro.core.gateway import TransferGateway
from repro.core.policy import OffloadPolicy, SchedulingPolicy, cc_aware_defaults
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import OffloadManager
from repro.serving.sampler import SamplingParams
from repro.trace import TraceRecorder
from repro.trace import opclasses as oc


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs.base import all_configs, smoke_config
    from repro.models.model import Model
    return Model(smoke_config(all_configs()["olmo-1b"]))


def _req(rid, prompt, n_tokens=2):
    return Request(rid, prompt=prompt,
                   sampling=SamplingParams(max_new_tokens=n_tokens))


# ---------------------------------------------------------------------------------
# Satellite 1: on_restore_done subscribers are exception-isolated
# ---------------------------------------------------------------------------------


class TestRestoreCallbackIsolation:
    def test_raising_subscriber_does_not_poison_peers(self):
        gw = TransferGateway(BridgeModel(TPU_V5E, cc_on=True),
                             cc_aware_defaults(True), pool_workers=2)
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             store_threshold=1, block_bytes=512)
        h = hash(("p", 0))
        mgr.observe(h)
        mgr.evict(h, payload_bytes=512)

        got = []

        def boom(key, done_t):
            raise RuntimeError("subscriber bug")

        # the raiser is FIRST: without isolation the recorder never runs
        # and the engine slot waiting on its notification strands
        mgr.on_restore_done.append(boom)
        mgr.on_restore_done.append(lambda key, t: got.append((key, t)))
        hits, nbytes = mgr.restore([h], key="r0")
        assert hits == 1 and nbytes == 512
        assert got and got[0][0] == "r0"
        assert mgr.stats.callback_errors == 1

    def test_fault_free_restore_counts_no_errors(self):
        gw = TransferGateway(BridgeModel(TPU_V5E, cc_on=True),
                             cc_aware_defaults(True), pool_workers=2)
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE,
                             store_threshold=1, block_bytes=512)
        h = hash(("p", 1))
        mgr.observe(h)
        mgr.evict(h, payload_bytes=512)
        mgr.restore([h], key="r0")
        assert mgr.stats.callback_errors == 0


# ---------------------------------------------------------------------------------
# Satellite 2: the engine drains on its own thread, so close() strands nothing
# ---------------------------------------------------------------------------------


class TestEngineShutdown:
    def test_worker_drain_starts_no_thread(self, tiny_model):
        """WORKER_DRAIN prices a worker-channel drain and drains inline:
        serving starts no thread, and every drain records ``worker_drain``."""
        before = set(threading.enumerate())

        def started():
            return set(threading.enumerate()) - before
        eng = ServingEngine(tiny_model, max_batch=2, max_len=32,
                            policy=SchedulingPolicy.WORKER_DRAIN, cc_on=True)
        with TraceRecorder(eng.gateway, label="inline") as rec:
            for i in range(3):
                eng.submit(_req(f"r{i}", [1, 2, 3 + i], n_tokens=4))
            stats = eng.run()
            assert not started()
        eng.close()
        assert not started()
        assert stats["finished"] == 3
        drains = rec.tape().op_class_mix().get(oc.WORKER_DRAIN, 0)
        assert drains == eng.step_count > 0

    def test_close_twice_is_harmless(self, tiny_model):
        eng = ServingEngine(tiny_model, max_batch=2, max_len=32,
                            policy=SchedulingPolicy.WORKER_DRAIN, cc_on=True)
        eng.submit(_req("r0", [1, 2, 3]))
        eng.run()
        eng.close()
        eng.close()
        assert [len(r.output_tokens) for r in eng.finished] == [2]


# ---------------------------------------------------------------------------------
# Satellite 3: spawn/close loop leaves fleet budgets at their high-water marks
# ---------------------------------------------------------------------------------


class TestLeakAudit:
    def test_spawn_close_loop_returns_all_leases_and_pages(self, tiny_model):
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        pinned = PinnedBudget(8 << 30)
        tm = TenantManager(TPU_V5E, cc_on=True)
        cfg = ReplicaConfig(max_batch=2, max_len=64)
        assert budget.allocated() == 0 and pinned.allocated() == 0
        for i in range(3):
            tenant = tm.provision(f"t{i}", 2)
            lease = budget.acquire(f"rep{i}", 4)
            please = pinned.acquire(f"rep{i}",
                                    cfg.pinned_bytes(lease.n_contexts))
            rep = Replica(f"rep{i}", tiny_model,
                          tenant, lease, BridgeModel(TPU_V5E, cc_on=True),
                          cfg, pinned_lease=please,
                          context_budget=budget, pinned_budget=pinned)
            assert rep.submit(_req(f"r{i}", list(range(1, 17))))
            # the live request holds pages; close() must hand them back
            assert len(rep.pages.free) < cfg.n_pages
            rep.close()
            rep.close()        # idempotent
            assert len(rep.pages.free) == cfg.n_pages
            assert budget.allocated() == 0, f"context lease leaked (iter {i})"
            assert pinned.allocated() == 0, f"pinned lease leaked (iter {i})"
            tm.decommission(tenant.tenant_id)

    def test_cluster_close_releases_budgets_too(self, tiny_model):
        """Router-side release composes with Replica.close() (idempotent)."""
        cluster = build_cluster(tiny_model, n_replicas=2,
                                replica_cfg=ReplicaConfig(max_batch=2,
                                                          max_len=64))
        cluster.submit(_req("r0", list(range(1, 17))))
        cluster.run()
        cluster.close()
        assert cluster.budget.allocated() == 0
        assert cluster.pinned_budget.allocated() == 0


# ---------------------------------------------------------------------------------
# Satellite 4a: the router never routes to an unattested replica
# ---------------------------------------------------------------------------------


class _HealthStub:
    """Routing surface plus the DESIGN.md §11 health gate."""

    def __init__(self, replica_id, *, attested=True, health="healthy"):
        self.replica_id = replica_id
        self.cfg = ReplicaConfig()
        self.attested = attested
        self.health = health
        self.submitted = []

    def routable(self):
        return self.health == "healthy" and self.attested

    def kv_inventory(self):
        return set()

    def load_score(self):
        return 0.0

    def pending(self):
        return len(self.submitted)

    def submit(self, req, prefix_hashes=None):
        self.submitted.append(req)
        return True


class TestAttestationGatedRouting:
    def test_unattested_replica_receives_nothing(self):
        bad = _HealthStub("bad", attested=False)
        good = _HealthStub("good")
        router = ClusterRouter([bad, good],
                               routing=RoutingPolicy.LEAST_LOADED)
        for i in range(4):
            assert router.submit(_req(f"r{i}", list(range(16)))) is good
        assert bad.submitted == []

    def test_quarantined_replica_receives_nothing(self):
        sick = _HealthStub("sick", health="quarantined")
        good = _HealthStub("good")
        router = ClusterRouter([sick, good],
                               routing=RoutingPolicy.PREFIX_AFFINITY)
        for i in range(3):
            assert router.submit(_req(f"r{i}", list(range(16)))) is good
        assert sick.submitted == []

    def test_no_eligible_replica_sheds_instead_of_misplacing(self):
        bad = _HealthStub("bad", attested=False)
        router = ClusterRouter([bad], routing=RoutingPolicy.LEAST_LOADED)
        assert router.submit(_req("r0", list(range(16)))) is None
        assert router.rejected == 1
        assert bad.submitted == []


# ---------------------------------------------------------------------------------
# Satellite 4b: the autoscaler must not spin-loop on BudgetExhausted
# ---------------------------------------------------------------------------------


class TestAutoscalerSpawnBackoff:
    def test_rejected_spawn_backs_off_instead_of_hammering(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True, limit=1)
        budget.acquire("existing", 1)
        calls = []

        def spawn_fn():
            calls.append(1)
            return budget.acquire(f"rep{len(calls)}", 1)   # BudgetExhausted

        scaler = Autoscaler(budget, AutoscalerConfig(spawn_backoff_s=1.0))
        assert scaler.try_spawn(spawn_fn, now=0.0) is None
        assert scaler.spawn_failures == 1 and len(calls) == 1
        for t in (0.1, 0.5, 0.9):
            assert scaler.try_spawn(spawn_fn, now=t) is None
        assert len(calls) == 1, "spawn re-invoked inside the backoff window"
        assert scaler.spawn_skipped == 3
        # window over: one more try, one more failure, backoff doubles
        assert scaler.try_spawn(spawn_fn, now=1.0) is None
        assert len(calls) == 2
        assert scaler.spawn_backoff_until == pytest.approx(3.0)

    def test_successful_spawn_resets_backoff(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True, limit=1)
        budget.acquire("existing", 1)
        scaler = Autoscaler(budget, AutoscalerConfig(spawn_backoff_s=1.0))

        def failing():
            return budget.acquire("rep", 1)

        assert scaler.try_spawn(failing, now=0.0) is None
        budget.release("existing")
        assert scaler.try_spawn(failing, now=2.0) is not None
        assert scaler.spawns == 1
        assert scaler.spawn_backoff_until == 0.0


# ---------------------------------------------------------------------------------
# Failover: drain-and-re-route with zero loss (the tentpole, end to end)
# ---------------------------------------------------------------------------------


class TestFailover:
    def test_failover_moves_work_and_loses_nothing(self, tiny_model):
        cluster = build_cluster(tiny_model, n_replicas=2,
                                replica_cfg=ReplicaConfig(max_batch=2,
                                                          max_len=64))
        prefix = list(range(1, 17))
        for i in range(4):
            assert cluster.submit(
                _req(f"r{i}", prefix + [60 + i] * 8, n_tokens=3)) is not None
        # a tick puts some requests in active slots, so the drain exercises
        # the engine's preemption path, not just queue surgery
        for r in cluster.replicas:
            r.tick()
        loaded = max(cluster.replicas, key=lambda r: r.pending())
        assert loaded.pending() > 0
        report = cluster.fail_replica(loaded.replica_id, reason="injected")
        assert report["drained"] == report["moved"] + report["requeued"]
        assert report["moved"] >= 1          # a healthy peer existed
        assert loaded.health == Replica.QUARANTINED
        assert loaded.pending() == 0         # nothing stranded on the source
        st = cluster.run()
        assert st["finished"] == 4           # zero requests lost
        assert st["failovers"] == 1
        # movers keep exactly one request_log entry, re-pointed at the target
        movers = [e for e in cluster.request_log if "failover_from" in e]
        assert len(movers) == report["moved"]
        assert all(e["replica_id"] != loaded.replica_id for e in movers)
        cluster.close()

    def test_failover_requeues_on_source_when_no_peer(self, tiny_model):
        cluster = build_cluster(tiny_model, n_replicas=1,
                                replica_cfg=ReplicaConfig(max_batch=2,
                                                          max_len=64))
        for i in range(2):
            assert cluster.submit(
                _req(f"r{i}", list(range(1, 17)))) is not None
        report = cluster.fail_replica("replica-0", reason="injected")
        assert report["requeued"] == report["drained"] == 2
        assert report["moved"] == 0
        # quarantine gates routing, not execution: the requeued work still
        # serves on the source — a failover can never hang a request
        st = cluster.run()
        assert st["finished"] == 2
        cluster.replicas[0].mark_healthy()
        assert cluster.replicas[0].routable()
        cluster.close()
