"""Cluster subsystem: budget, tenant manager, router, autoscaler, inventory
exports, and an end-to-end two-tenant serving run."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.cluster import (Autoscaler, AutoscalerConfig, BudgetExhausted,
                           ReplicaConfig, ReplicaMetrics, RoutingPolicy,
                           ScaleDecision, SecureContextBudget, build_cluster,
                           prompt_prefix_hashes)
from repro.cluster.router import ClusterRouter
from repro.cluster.tenant_manager import (AttestationError, TenantManager)
from repro.core.bridge import TPU_V5E, BridgeModel
from repro.core.fabric import AttestationEvidence
from repro.core.gateway import TransferGateway
from repro.core.policy import OffloadPolicy, cc_aware_defaults
from repro.serving.engine import Request
from repro.serving.kv_cache import PagePool
from repro.serving.offload import OffloadManager
from repro.serving.sampler import SamplingParams

SRC = Path(__file__).resolve().parents[1] / "src"


class TestSecureContextBudget:
    def test_limit_comes_from_profile(self):
        b = SecureContextBudget(TPU_V5E, cc_on=True)
        assert b.limit == TPU_V5E.max_secure_contexts

    def test_cc_off_is_unconstrained(self):
        b = SecureContextBudget(TPU_V5E, cc_on=False)
        lease = b.acquire("r0", 999)
        assert lease.n_contexts == 999
        assert b.available() == float("inf")

    def test_partial_grant_then_exhaustion(self):
        b = SecureContextBudget(TPU_V5E, cc_on=True)   # limit 16
        assert b.acquire("r0", 12).n_contexts == 12
        assert b.acquire("r1", 12).n_contexts == 4     # shrinks to remainder
        with pytest.raises(BudgetExhausted):
            b.acquire("r2", 1)
        b.release("r1")
        assert b.acquire("r2", 2).n_contexts == 2

    def test_double_lease_rejected(self):
        b = SecureContextBudget(TPU_V5E, cc_on=True)
        b.acquire("r0", 2)
        with pytest.raises(ValueError):
            b.acquire("r0", 2)

    def test_fair_share_redistributes_not_multiplies(self):
        b = SecureContextBudget(TPU_V5E, cc_on=True)   # limit 16
        assert b.fair_share(2, 8) == [8, 8]
        assert b.fair_share(4, 8) == [4, 4, 4, 4]
        assert b.fair_share(8, 8) == [2] * 8
        for n in (2, 4, 8):
            assert sum(b.fair_share(n, 8)) <= b.limit

    def test_fair_share_over_limit_raises(self):
        b = SecureContextBudget(TPU_V5E, cc_on=True, limit=4)
        with pytest.raises(BudgetExhausted):
            b.fair_share(5, 1)


class TestTenantManager:
    def test_provision_two_isolated_tenants(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        a = tm.provision("a", 2)
        b = tm.provision("b", 2)
        assert not (set(a.visible_devices()) & set(b.visible_devices()))
        assert tm.isolation_report()["isolated"]
        assert all(rec.attested for rec in tm.records)

    def test_capacity_by_partition_vocabulary(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        assert tm.capacity(2) == 4
        tm.provision("a", 2)
        assert tm.capacity(2) == 3
        assert tm.capacity(8) == 0       # the 8-wide shape now conflicts

    def test_attestation_gate_blocks_bad_evidence(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        bad = AttestationEvidence(device_cc_mode=False)
        with pytest.raises(AttestationError):
            tm.provision("a", 2, evidence=bad)
        assert "a" not in tm.fm.active   # rolled back

    def test_attestation_gap_is_reported_not_trusted(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        t = tm.provision("a", 2)
        report = tm.attest(t)
        assert report["ok"]
        assert "fabric_manager_identity" in report["gap"]
        assert "switch_routing_tables" in report["gap"]

    def test_stale_partition_health_gate(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        for p in tm.fm.partitions:
            tm.fm.mark_stale(p.partition_id)
        with pytest.raises(RuntimeError):
            tm.provision("a", 2)

    def test_control_plane_timing_accumulates(self):
        tm = TenantManager(TPU_V5E, cc_on=True)
        tm.provision("a", 2)
        after_activate = tm.control_plane_seconds
        assert 10.0 <= after_activate <= 20.0
        tm.decommission("a")
        assert tm.control_plane_seconds > after_activate


class _StubReplica:
    """Just enough surface for ClusterRouter routing decisions."""

    def __init__(self, replica_id, inventory, load):
        self.replica_id = replica_id
        self.cfg = ReplicaConfig()
        self._inventory = set(inventory)
        self._load = load
        self.submitted = []

    def kv_inventory(self):
        return self._inventory

    def load_score(self):
        return self._load

    def pending(self):
        return 0

    def submit(self, req, prefix_hashes=None):
        self.submitted.append(req)
        return True


def _req(rid, prompt):
    return Request(rid, prompt=prompt,
                   sampling=SamplingParams(max_new_tokens=2))


class TestRouterRouting:
    def test_prefix_affinity_prefers_inventory_overlap(self):
        prompt = list(range(16)) + [99] * 4
        hashes = prompt_prefix_hashes(prompt, 8)
        warm = _StubReplica("warm", hashes, load=100.0)
        cold = _StubReplica("cold", [], load=0.0)
        router = ClusterRouter([cold, warm],
                               routing=RoutingPolicy.PREFIX_AFFINITY)
        assert router.submit(_req("r0", prompt)) is warm
        assert router.affinity_hits == 1

    def test_affinity_falls_back_to_least_loaded(self):
        busy = _StubReplica("busy", [], load=5.0)
        idle = _StubReplica("idle", [], load=1.0)
        router = ClusterRouter([busy, idle],
                               routing=RoutingPolicy.PREFIX_AFFINITY)
        assert router.submit(_req("r0", list(range(16)))) is idle
        assert router.affinity_hits == 0

    def test_least_loaded_breaks_ties_round_robin(self):
        a = _StubReplica("a", [], load=1.0)
        b = _StubReplica("b", [], load=1.0)
        router = ClusterRouter([a, b], routing=RoutingPolicy.LEAST_LOADED)
        picks = [router.submit(_req(f"r{i}", list(range(16)))).replica_id
                 for i in range(4)]
        assert picks == ["a", "b", "a", "b"]

    def test_admission_control_sheds_load(self):
        class Backed(_StubReplica):
            def pending(self):
                return 10

        router = ClusterRouter([Backed("a", [], 1.0)],
                               routing=RoutingPolicy.LEAST_LOADED,
                               max_cluster_queue=5)
        assert router.submit(_req("r0", [1, 2, 3])) is None
        assert router.rejected == 1


def _metrics(rid, delay, vt=10.0, bridge=0.0):
    return ReplicaMetrics(replica_id=rid, queued=1, active=1,
                          queue_delay_s=delay, virtual_time_s=vt,
                          bridge_time_s=bridge,
                          op_class_seconds={"drain_d2h": bridge})


class TestAutoscaler:
    CFG = AutoscalerConfig(high_queue_delay_s=0.1, low_queue_delay_s=0.01,
                           min_replicas=1, max_replicas=4,
                           bridge_bound_fraction=0.5)

    def test_scales_up_on_queue_delay(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        sc = Autoscaler(budget, self.CFG)
        out = sc.evaluate([_metrics("a", 0.5), _metrics("b", 0.3)])
        assert out["decision"] is ScaleDecision.SCALE_UP
        assert out["target_replicas"] == 3

    def test_scales_down_when_idle(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        sc = Autoscaler(budget, self.CFG)
        out = sc.evaluate([_metrics("a", 0.0), _metrics("b", 0.0)])
        assert out["decision"] is ScaleDecision.SCALE_DOWN

    def test_holds_in_band_and_at_min(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        sc = Autoscaler(budget, self.CFG)
        assert sc.evaluate([_metrics("a", 0.05)])["decision"] \
            is ScaleDecision.HOLD
        assert sc.evaluate([_metrics("a", 0.0)])["decision"] \
            is ScaleDecision.HOLD   # already at min_replicas

    def test_bridge_bound_when_budget_exhausted(self):
        """L4 as an autoscaling invariant: no contexts left + crossings
        dominate => scaling up only redistributes bridge bandwidth."""
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        budget.acquire("fleet", budget.limit)
        sc = Autoscaler(budget, self.CFG)
        out = sc.evaluate([_metrics("a", 0.5, vt=10.0, bridge=8.0)])
        assert out["decision"] is ScaleDecision.BRIDGE_BOUND
        assert out["target_replicas"] == 1
        assert out["bridge_fraction"] == pytest.approx(0.8)

    def test_compute_bound_still_scales_up_when_budget_left(self):
        budget = SecureContextBudget(TPU_V5E, cc_on=True)
        sc = Autoscaler(budget, self.CFG)
        out = sc.evaluate([_metrics("a", 0.5, vt=10.0, bridge=8.0)])
        assert out["decision"] is ScaleDecision.SCALE_UP


class TestInventoryExports:
    def test_page_pool_inventory_tracks_allocated_hashes(self):
        pool = PagePool(16, 8, 2, 16, 2)
        blocks = [(1, 2, 3), (4, 5, 6)]
        table = pool.allocate("a", 16, token_blocks=blocks)
        assert pool.inventory() == {hash(b) for b in blocks}
        pool.release(table)
        assert pool.inventory() == set()

    def test_page_reuse_drops_stale_hashes(self):
        """A page reallocated for unhashed content must not keep advertising
        the previous occupant's hash (would mislead prefix-affinity)."""
        pool = PagePool(2, 8, 2, 16, 2)
        table = pool.allocate("a", 16, token_blocks=[(1, 2), (3, 4)])
        pool.release(table)
        pool.allocate("b", 16)               # same pages, no token_blocks
        assert pool.inventory() == set()

    def test_offload_inventory_is_host_store(self):
        gw = TransferGateway(BridgeModel(TPU_V5E, cc_on=True),
                             cc_aware_defaults(True), pool_workers=4)
        mgr = OffloadManager(gw, OffloadPolicy.REUSE_AWARE, store_threshold=2)
        h = hash(("p", 0))
        mgr.observe(h)
        mgr.observe(h)
        mgr.evict(h, payload_bytes=512)
        assert mgr.inventory() == {h}


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs.base import all_configs, smoke_config
    from repro.models.model import Model
    return Model(smoke_config(all_configs()["olmo-1b"]))


_FOUR_DEVICE_CLUSTER = """
import jax
from repro.cluster import build_cluster
from repro.configs.base import get_config, smoke_config
from repro.models.model import Model
from repro.serving.engine import Request
from repro.serving.sampler import SamplingParams

cluster = build_cluster(Model(smoke_config(get_config("olmo-1b"))),
                        n_replicas=4, partition_size=1)
last = cluster.replicas[-1]
last.submit(Request("r0", prompt=[1, 2, 3, 4, 5],
                    sampling=SamplingParams(max_new_tokens=2)))
assert cluster.run()["finished"] == 1 and last.engine.finished
for r in cluster.replicas:
    homes = {d for leaf in jax.tree.leaves((r.engine.params, r.engine.caches))
             for d in leaf.devices()}
    print(r.replica_id, *sorted(map(str, homes)))
cluster.close()
"""


class TestClusterEndToEnd:
    def test_replicas_land_on_their_own_devices(self):
        """On a four-device host each replica's params and cache sit on
        its own device, and the last replica's stay there after it serves
        a request."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join(
                       [str(SRC), os.environ.get("PYTHONPATH", "")]))
        t0 = time.perf_counter()
        out = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_CLUSTER],
                             env=env, capture_output=True, text=True,
                             timeout=120)
        elapsed = time.perf_counter() - t0
        assert out.returncode == 0, out.stderr[-2000:]
        rows = [line.split() for line in out.stdout.splitlines()]
        assert len(rows) == 4
        assert all(len(row) == 2 for row in rows), rows   # one device each
        assert len({row[1] for row in rows}) == 4, rows
        assert elapsed < 30.0, f"{elapsed:.1f} s"

    def test_two_tenants_serve_concurrently_and_stay_isolated(self, tiny_model):
        cluster = build_cluster(tiny_model, cc_on=True, n_replicas=2,
                                partition_size=2,
                                routing=RoutingPolicy.LEAST_LOADED)
        prefix = list(range(1, 17))
        for i in range(4):
            cluster.submit(Request(f"r{i}", prompt=prefix + [50 + i] * 8,
                                   sampling=SamplingParams(max_new_tokens=3)))
        for r in cluster.replicas:
            r.tick()
        assert all(r.engine.active or r.engine.queue for r in cluster.replicas)
        assert cluster.tenant_manager.isolation_report()["isolated"]
        st = cluster.run()
        assert st["finished"] == 4
        assert st["isolation"]["isolated"]
        # budget honored: leases never exceed the system-wide limit
        assert sum(st["leased_contexts"]) <= TPU_V5E.max_secure_contexts
        cluster.close()
        assert cluster.tenant_manager.isolation_report()["tenants"] == {}

    def test_prefix_affinity_restores_warm_prefix(self, tiny_model):
        cluster = build_cluster(tiny_model, cc_on=True, n_replicas=2,
                                partition_size=2,
                                routing=RoutingPolicy.PREFIX_AFFINITY)
        prefix = list(range(1, 17))
        for i in range(5):
            cluster.submit(Request(f"r{i}", prompt=prefix + [90 + i] * 8,
                                   sampling=SamplingParams(max_new_tokens=2)))
            cluster.run()
        st = cluster.stats()
        assert st["affinity_hits"] >= 1
        assert st["warm_blocks_restored"] >= 2
        # warm requests are strictly cheaper on the virtual clock
        ttfts = {t["request_id"]: t for t in cluster.ttfts()}
        assert ttfts["r4"]["warm_blocks"] > 0
        assert ttfts["r4"]["ttft_s"] < ttfts["r0"]["ttft_s"]
        cluster.close()

    def test_cluster_determinism_across_runs(self, tiny_model,
                                             deterministic_seed):
        def outputs():
            cluster = build_cluster(tiny_model, cc_on=True, n_replicas=2,
                                    partition_size=2,
                                    routing=RoutingPolicy.PREFIX_AFFINITY,
                                    seed=deterministic_seed)
            for i in range(3):
                cluster.submit(Request(
                    f"r{i}", prompt=list(range(1, 17)) + [70 + i] * 8,
                    sampling=SamplingParams(max_new_tokens=3)))
                cluster.run()
            toks = {t["request_id"]: t["ttft_s"] for t in cluster.ttfts()}
            placement = [e["replica_id"] for e in cluster.request_log]
            cluster.close()
            return toks, placement

        assert outputs() == outputs()

    def test_replica_tapes_are_exported_and_conformant(self, tiny_model,
                                                       deterministic_seed):
        """Every replica's crossing stream is a replayable, law-abiding
        tape — the cluster-level form of the §5.2 evidence."""
        from repro.trace import TraceReplayer, ReplaySpec, check_tape
        cluster = build_cluster(tiny_model, cc_on=True, n_replicas=2,
                                partition_size=2,
                                routing=RoutingPolicy.PREFIX_AFFINITY,
                                seed=deterministic_seed)
        prefix = list(range(1, 17))
        for i in range(4):
            cluster.submit(Request(f"r{i}", prompt=prefix + [40 + i] * 8,
                                   sampling=SamplingParams(max_new_tokens=2)))
            cluster.run()
        tapes = [r.tape() for r in cluster.replicas]
        served = [t for t in tapes if t.n_crossings()]
        assert served
        for replica, tape in zip(cluster.replicas, tapes):
            assert tape.meta.label == f"replica-{replica.replica_id}"
            assert tape.meta.pool_workers == replica.lease.n_contexts
            report = check_tape(tape)
            assert report.ok, report.format()
            # metrics' per-op-class accounting is the tape's view
            assert replica.metrics().op_class_seconds == tape.op_class_seconds()
        # the spill/restore classes of the churn path appear on the tape
        mix = {}
        for t in served:
            for k, v in t.op_class_mix().items():
                mix[k] = mix.get(k, 0) + v
        assert "kv_spill_d2h" in mix
        # replica tapes re-price like any other tape (CC tax is positive)
        result = TraceReplayer(served[0]).reprice(ReplaySpec(cc_on=False))
        assert result.gap_s > 0
        cluster.close()
