"""Property-based tests for slot-masked decode.

The invariant that carries the refactor: deferral is *caused* by pending
restores and nothing else.  Under any interleaving of late restores (random
subset of running requests, random pipeline depths) a step never defers
more slots than it has restores still draining, deferred slots always
rejoin (every request finishes), and the engine's deferral accounting is
conserved between the per-step trace and the scheduler stats.
"""


import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.bridge import TPU_V5E, BridgeModel
from repro.core.compute import ComputeModel
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.sampler import SamplingParams
from repro.trace.harness import smoke_model

MODEL = smoke_model()


@settings(max_examples=10, deadline=None)
@given(
    n_requests=st.integers(min_value=1, max_value=3),
    restored=st.sets(st.integers(min_value=0, max_value=2), max_size=3),
    blocks=st.integers(min_value=4, max_value=64),
)
def test_deferred_slots_never_exceed_pending_restores(n_requests, restored,
                                                      blocks):
    bridge = BridgeModel(TPU_V5E, cc_on=True)
    engine = ServingEngine(
        MODEL, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN, bridge=bridge,
        defaults=cc_aware_defaults(True, concurrency=4),
        compute_model=ComputeModel(MODEL.cfg, bridge), seed=0)
    engine.gateway.pool.prewarm()
    for i in range(n_requests):
        engine.submit(Request(f"r{i}", prompt=[1, 2, 3],
                              sampling=SamplingParams(max_new_tokens=6)))
    engine.step()                              # everyone running
    marked = {f"r{i}" for i in restored if i < n_requests}
    if marked:
        mgr = OffloadManager(engine.gateway, OffloadPolicy.REUSE_AWARE,
                             pipelined_restore=True,
                             restore_chunk_bytes=8 << 10)
        for b in range(blocks):
            mgr.host_store[b] = HostBlock(b, 64 << 10, 2, None)
        mgr.on_restore_done.append(engine.mark_restore)
        for key in sorted(marked):
            mgr.restore(list(range(blocks)), key=key)
    stats = engine.run()
    engine.close()

    # every request finishes: deferred slots always rejoin
    assert stats["finished"] == n_requests
    # a step can only defer slots whose restores were still draining —
    # bounded by the number of restores ever marked, per step and in total
    assert all(t.deferred <= len(marked) for t in engine.trace)
    assert all(t.active >= 1 for t in engine.trace)
    # accounting conservation: per-step trace == scheduler stats
    assert (sum(t.deferred for t in engine.trace)
            == stats["overlap"]["deferred_slots"])
    # no restores marked -> the mask never engages
    if not marked:
        assert stats["overlap"]["deferred_slots"] == 0
