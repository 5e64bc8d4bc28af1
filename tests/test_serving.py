"""Serving engine / scheduler / offload / loader integration tests."""

import jax
import numpy as np
import pytest

from repro.configs.base import all_configs, smoke_config
from repro.core.bridge import B300, RTX_PRO_6000, TPU_V5E, BridgeModel
from repro.core.gateway import TransferGateway
from repro.core.policy import OffloadPolicy, SchedulingPolicy as SP, cc_aware_defaults
from repro.launch.serve import greedy_reference, seeded_prompts, serve
from repro.loader.pooled_loader import LoaderVariant, PooledLoader
from repro.loader.sharded_weights import ShardedCheckpoint, save_sharded
from repro.models.model import Model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import PagePool, block_table_array
from repro.serving.offload import OffloadManager, churn_workload
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import Scheduler, SchedulerConfig


@pytest.fixture(scope="module")
def tiny_model():
    return Model(smoke_config(all_configs()["olmo-1b"]))


class TestEngine:
    def test_serves_requests_all_policies(self, tiny_model):
        for pol in (SP.SYNC_DRAIN, SP.ASYNC_OVERLAP, SP.WORKER_DRAIN):
            eng = ServingEngine(tiny_model, max_batch=4, max_len=64,
                                policy=pol, cc_on=True)
            for i in range(6):
                eng.submit(Request(f"r{i}", prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=6)))
            stats = eng.run()
            eng.close()
            assert stats["finished"] == 6
            assert stats["total_tokens"] == 36

    def test_deterministic_outputs_across_policies(self, tiny_model,
                                                   deterministic_seed):
        """Scheduling policy changes timing, never tokens."""
        outs = {}
        for pol in (SP.SYNC_DRAIN, SP.ASYNC_OVERLAP):
            eng = ServingEngine(tiny_model, max_batch=2, max_len=64,
                                policy=pol, cc_on=True,
                                seed=deterministic_seed)
            eng.submit(Request("r0", prompt=[5, 6, 7],
                               sampling=SamplingParams(max_new_tokens=8)))
            eng.run()
            outs[pol] = eng.finished[0].output_tokens
            eng.close()
        assert outs[SP.SYNC_DRAIN] == outs[SP.ASYNC_OVERLAP]

    def test_policy_inversion_on_virtual_clock(self, tiny_model):
        """The real engine shows the inversion end-to-end: async costs more
        bridge time CC-on; CC-off it does not pay the fresh-staging tax."""
        times = {}
        for cc in (False, True):
            for pol in (SP.SYNC_DRAIN, SP.ASYNC_OVERLAP):
                eng = ServingEngine(tiny_model, max_batch=4, max_len=64,
                                    policy=pol, cc_on=cc, seed=3)
                for i in range(4):
                    eng.submit(Request(f"r{i}", prompt=[1, 2],
                                       sampling=SamplingParams(max_new_tokens=6)))
                eng.run()
                times[(pol, cc)] = eng.stats()["bridge_time_s"]
                eng.close()
        on_ratio = times[(SP.ASYNC_OVERLAP, True)] / times[(SP.SYNC_DRAIN, True)]
        off_ratio = times[(SP.ASYNC_OVERLAP, False)] / times[(SP.SYNC_DRAIN, False)]
        assert on_ratio > 5.0          # CC-on: async pays the 44x class
        assert off_ratio < on_ratio    # CC-off: the tax collapses

    def test_scheduler_completes_with_queue_pressure(self, tiny_model):
        eng = ServingEngine(tiny_model, max_batch=2, max_len=64,
                            policy=SP.SYNC_DRAIN, cc_on=True)
        sched = Scheduler(eng, SchedulerConfig())
        for i in range(8):
            sched.submit(Request(f"r{i}", prompt=[1, 2, 3],
                                 sampling=SamplingParams(max_new_tokens=4)))
        stats = sched.run()
        assert stats["finished"] == 8


class TestServeEntryPoint:
    """The contract chip_smoke.py checks on the chip, at smoke size."""

    def test_engine_tokens_equal_straight_line_loop(self):
        cfg = smoke_config(all_configs()["olmo-1b"])
        # four prompts fill the four slots at once, so every packed step
        # holds the same rows as the reference's fixed batch
        prompts = seeded_prompts(4, cfg.vocab_size, min_len=4, max_len=40,
                                 seed=1)
        stats, finished = serve(cfg, prompts, max_batch=4, max_len=96,
                                max_new_tokens=8, seed=3, cc_on=True)
        assert stats["finished"] == len(prompts)
        assert stats["preemptions"] == 0
        assert [r.prompt for r in finished] == prompts
        ref, finite = greedy_reference(
            cfg, Model(cfg).init(jax.random.PRNGKey(3)), prompts,
            max_new_tokens=8, max_len=96)
        assert finite
        assert [r.output_tokens for r in finished] == ref


class _FakeEngine:
    """Minimal engine surface for deterministic Scheduler tests."""

    def __init__(self, requests):
        self.step_compiled = False
        self.step_admitted = 0
        self.span_attrs = {}
        self.queue = []
        self.active = {i: r for i, r in enumerate(requests)}
        for r in self.active.values():
            r.state = "running"
        self.released = []

    def step(self):
        return len(self.active)

    def _release(self, req, *, state="finished"):
        for slot, r in list(self.active.items()):
            if r is req:
                del self.active[slot]
        req.state = state
        self.released.append((req.request_id, state))
        if state != "finished":
            req.restarts += 1
            self.queue.append(req)


def _running_request(rid, enqueue_t):
    r = Request(rid, prompt=[1, 2, 3])
    r.enqueue_t = enqueue_t
    return r


class TestSchedulerPreemption:
    def test_straggler_preempts_newest_after_patience(self, monkeypatch):
        """A step slower than straggler_factor x EMA for `patience`
        consecutive ticks preempts the newest request (LIFO)."""
        eng = _FakeEngine([_running_request("old", 0.0),
                           _running_request("new", 5.0)])
        sched = Scheduler(eng, SchedulerConfig(straggler_factor=4.0,
                                               patience=2))
        # perf_counter pairs per tick: 3 fast baseline ticks, then two
        # escalating stragglers (escalation keeps dt ahead of the EMA)
        times = iter([0.0, 1.0,           # dt=1     (EMA seed)
                      2.0, 3.0,           # dt=1
                      4.0, 5.0,           # dt=1
                      6.0, 106.0,         # dt=100   slow #1
                      110.0, 1110.0])     # dt=1000  slow #2 -> preempt
        monkeypatch.setattr("repro.serving.scheduler.time.perf_counter",
                            lambda: next(times))
        for _ in range(5):
            sched.tick()
        assert sched.preemptions == 1
        assert eng.released == [("new", "preempted")]   # newest, not oldest
        assert eng.queue and eng.queue[0].request_id == "new"

    def test_compiling_steps_are_not_stragglers(self, monkeypatch):
        """Ticks on which the engine compiled are neither timed into the
        EMA nor counted toward a straggler streak."""
        eng = _FakeEngine([_running_request("old", 0.0),
                           _running_request("new", 5.0)])
        sched = Scheduler(eng, SchedulerConfig(straggler_factor=4.0,
                                               patience=1))
        times = iter([0.0, 1.0,           # dt=1     (EMA seed)
                      2.0, 102.0,         # dt=100   compiled
                      110.0, 1110.0,      # dt=1000  compiled
                      1200.0, 1201.0])    # dt=1
        monkeypatch.setattr("repro.serving.scheduler.time.perf_counter",
                            lambda: next(times))
        for compiled in (False, True, True, False):
            eng.step_compiled = compiled
            sched.tick()
        assert sched.preemptions == 0
        assert sched._ema_dt == pytest.approx(1.0)

    def test_engine_flags_steps_that_meet_a_new_shape(self, tiny_model):
        eng = ServingEngine(tiny_model, max_batch=4, max_len=64)
        flags = []

        def step():
            eng.step()
            flags.append(eng.step_compiled)

        eng.submit(Request("a", prompt=[1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=6)))
        step()                            # new prompt length + width 1
        step()                            # same shapes
        eng.submit(Request("b", prompt=[4, 5, 6],
                           sampling=SamplingParams(max_new_tokens=6)))
        step()                            # width 2 is new
        eng.submit(Request("c", prompt=[7, 8],
                           sampling=SamplingParams(max_new_tokens=6)))
        step()                            # new prompt length, width 4 new
        step()                            # same shapes
        eng.close()
        assert flags == [True, False, True, True, False]

    def test_pool_exhaustion_preempts_lifo(self):
        """Newest requests yield pages first; oldest survive (vLLM order)."""
        eng = _FakeEngine([_running_request("r0", 0.0),
                           _running_request("r1", 1.0),
                           _running_request("r2", 2.0)])
        sched = Scheduler(eng)
        pool = PagePool(n_pages=6, page_size=8, n_kv_heads=1, head_dim=1,
                        n_layers=1)
        tables = {rid: pool.allocate(rid, 16) for rid in ("r0", "r1", "r2")}
        assert not pool.free                      # exhausted
        victims = sched.preempt_for_pool(pool, n_tokens=32, tables=tables)
        assert victims == ["r2", "r1"]            # strictly newest-first
        assert "r0" in tables                     # oldest keeps its pages
        assert len(pool.free) >= pool.pages_needed(32)
        assert sched.preemptions == 2
        assert [rid for rid, st in eng.released] == ["r2", "r1"]

    def test_pool_preemption_stops_without_progress(self):
        """A pageless newest request ends the scan unharmed: preempting it
        would free nothing, so its work is not destroyed."""
        eng = _FakeEngine([_running_request("r0", 0.0)])
        sched = Scheduler(eng)
        pool = PagePool(n_pages=2, page_size=8, n_kv_heads=1, head_dim=1,
                        n_layers=1)
        pool.allocate("other", 16)                # exhaust with untracked pages
        victims = sched.preempt_for_pool(pool, n_tokens=64, tables={})
        assert victims == []
        assert sched.preemptions == 0
        assert eng.active                         # r0 keeps running
        assert not pool.free                      # nothing was recoverable


class TestPagePool:
    def test_alloc_release_cycle(self):
        pool = PagePool(n_pages=16, page_size=8, n_kv_heads=2, head_dim=16,
                        n_layers=2)
        t1 = pool.allocate("a", 40)          # 5 pages
        assert len(t1) == 5
        assert pool.utilization() == pytest.approx(5 / 16)
        t2 = pool.allocate("b", 100)         # 13 pages > 11 free
        assert t2 is None
        pool.release(t1)
        assert pool.utilization() == 0.0

    def test_block_table_batch(self):
        pool = PagePool(16, 8, 2, 16, 2)
        ta = pool.allocate("a", 16)
        tb = pool.allocate("b", 24)
        arr = block_table_array({"a": ta, "b": tb}, ["a", "b"], pages_max=4)
        assert arr.shape == (2, 4)
        assert list(arr[0, :2]) == ta

    def test_content_hash_reuse_counting(self):
        pool = PagePool(16, 8, 2, 16, 2)
        blocks = [(1, 2, 3), (4, 5, 6)]
        pool.allocate("a", 16, token_blocks=blocks)
        pool.allocate("b", 16, token_blocks=blocks)
        assert pool.seen_counts[hash(blocks[0])] == 2


class TestOffload:
    def _manager(self, policy):
        gw = TransferGateway(BridgeModel(RTX_PRO_6000, cc_on=True),
                             cc_aware_defaults(True), pool_workers=8)
        return OffloadManager(gw, policy, store_threshold=2)

    def test_reuse_aware_cuts_spill_by_orders_of_magnitude(self):
        shape = dict(n_requests=8, prefix_blocks=36, unique_blocks=4600,
                     block_bytes=64 * 1024, churn=3)
        default = churn_workload(self._manager(OffloadPolicy.SPILL_ALL), **shape)
        reuse = churn_workload(self._manager(OffloadPolicy.REUSE_AWARE), **shape)
        assert default.spilled_bytes > 500 * reuse.spilled_bytes
        assert reuse.spilled_bytes < 4 * (1 << 20)   # MiB scale

    def test_restore_hits_shared_prefix(self):
        mgr = self._manager(OffloadPolicy.REUSE_AWARE)
        h = hash(("p", 0))
        mgr.observe(h)
        mgr.observe(h)
        assert mgr.evict(h, payload_bytes=1024)
        hits, nbytes = mgr.restore([h])
        assert hits == 1 and nbytes == 1024

    def test_no_offload_never_spills(self):
        mgr = self._manager(OffloadPolicy.NO_OFFLOAD)
        mgr.observe(1)
        mgr.observe(1)
        assert not mgr.evict(1, payload_bytes=1024)


class TestLoader:
    def test_real_tensors_load_exactly(self, tmp_path):
        tensors = {f"w{i}": np.random.default_rng(i).standard_normal(
            (32, 16)).astype(np.float32) for i in range(6)}
        save_sharded(str(tmp_path / "ckpt"), tensors, n_shards=3)
        ckpt = ShardedCheckpoint(str(tmp_path / "ckpt"))
        loader = PooledLoader(BridgeModel(B300, cc_on=True), n_workers=8)
        for v in LoaderVariant:
            loaded, breakdown = loader.load(ckpt, v)
            for name, arr in tensors.items():
                np.testing.assert_array_equal(np.asarray(loaded[name]), arr)

    def test_ladder_is_monotone_at_model_scale(self):
        """Fixed lifecycle costs only amortize at real model sizes — the
        ladder ordering is a large-model property (59 GiB here)."""
        GIB = 1 << 30
        loader = PooledLoader(BridgeModel(B300, cc_on=True), n_workers=8)
        t = {v: loader.modeled_load_time(59 * GIB, 15, v)["total"]
             for v in LoaderVariant}
        assert t[LoaderVariant.PREWARMED] < t[LoaderVariant.POOLED] \
            < t[LoaderVariant.FASTSAFETENSORS] < t[LoaderVariant.THREADS8] \
            < t[LoaderVariant.NAIVE_POOL] < t[LoaderVariant.BASELINE]

    def test_ladder_transfers_across_platforms_within_5pct(self):
        """§6.1 headline: every stage within 5% across Blackwell platforms."""
        GIB = 1 << 30
        for variant in (LoaderVariant.BASELINE, LoaderVariant.FASTSAFETENSORS,
                        LoaderVariant.POOLED, LoaderVariant.PREWARMED):
            t = {}
            for prof in (B300, RTX_PRO_6000):
                loader = PooledLoader(BridgeModel(prof, cc_on=True), n_workers=8)
                t[prof.name] = loader.modeled_load_time(59 * GIB, 15, variant)["total"]
            assert abs(t["b300-hgx"] - t["rtx-pro-6000"]) / t["b300-hgx"] < 0.05
