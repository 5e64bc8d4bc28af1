"""One packed decode step in flight (DESIGN.md §10): the engine samples
inside the packed step, keeps each slot's last token on the device, and
dispatches step N+1 before it drains step N wherever no row can end in
step N.

The reference is the same engine with the lookahead off: every request
given a stop token no vocabulary holds, so that every step may end a row
and drains in its own tick.  The laws pinned here:

  * token streams are bitwise those of the reference, under greedy and
    under temperature with top-k (the key splits in the same order), in an
    MoE model (with the same assignment counts), with slots deferred by a
    pending restore, and at the ladder's forced full width;
  * the bridge tape, the virtual clock, each step's record, each
    request's first-token and finish times and the observatory's time of
    every token are those of the reference: a drain is priced when its
    step is dispatched, as the reference prices it, and its tokens take
    that time;
  * no tick hands a running request two tokens: before a step that can
    end a row, a running one or one an admission adds, a tick lands the
    step in flight alone;
  * a release from outside the step (a preemption) and ``close()`` land the
    step in flight first;
  * ``stats()["lookahead_steps"]`` counts the steps dispatched while their
    predecessor was in flight: nearly every step of long outputs, none
    when every row has a stop token.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core.bridge import B300, BridgeModel
from repro.core.compute import ComputeModel
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.models.model import Model
from repro.resilience import DegradationLadder, FaultInjector, FaultPlan
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.sampler import SamplingParams, sample
from repro.trace import TraceRecorder
from repro.trace.harness import smoke_model

#: a stop token no vocabulary holds: every step may end a row, so none
#: runs ahead
NEVER = 10**6


@pytest.fixture(scope="module")
def tiny():
    return smoke_model()


def _engine(model, *, policy=SP.WORKER_DRAIN, max_batch=4, max_len=64,
            compute=None):
    bridge = BridgeModel(B300, cc_on=True)
    return ServingEngine(
        model, max_batch=max_batch, max_len=max_len, policy=policy,
        bridge=bridge,
        defaults=dataclasses.replace(cc_aware_defaults(True),
                                     observability=True),
        compute_model=compute and ComputeModel(get_config(compute), bridge),
        seed=5)


def _requests(n, stop, *, base=6, step=3, **sampling):
    return [Request(f"r{i}", prompt=[1 + i, 5, 9 + 2 * i],
                    sampling=SamplingParams(max_new_tokens=base + step * i,
                                            stop_token=stop, **sampling))
            for i in range(n)]


def _spy_widths(eng) -> list:
    step, widths = eng._packed_decode, []

    def spy(params, caches, tokens, index, slots):
        widths.append(len(slots))
        return step(params, caches, tokens, index, slots)
    eng._packed_decode = spy
    return widths


def _serve(eng, reqs, before_run=None):
    """Submit ``reqs``, run ``eng`` to the end under a tape recorder and
    close it; returns what a run shows: token streams, first-token and
    finish times, the observatory's request spans and token times, step
    records, the tape's records and the clock."""
    for r in reqs:
        eng.submit(r)
    with TraceRecorder(eng.gateway, label="lookahead") as rec:
        if before_run is not None:
            before_run(eng)
        eng.run()
    eng.close()
    return {
        "tokens": {r.request_id: list(r.output_tokens) for r in reqs},
        "times": {r.request_id: (r.first_token_t, r.finish_t) for r in reqs},
        "spans": (eng.obs.spans.snapshot(),
                  {k: list(v.token_times)
                   for k, v in eng.obs.spans.spans.items()}),
        "steps": [dataclasses.asdict(t) for t in eng.trace],
        "tape": json.dumps([r.to_dict() for r in rec.tape().records],
                           sort_keys=True),
        "clock": eng.clock.now,
    }


def _both(make, reqs_of, before_run=None):
    """(engine, run) with the lookahead, then the same for the reference."""
    out = []
    for stop in (-1, NEVER):
        eng = make()
        out.append((eng, _serve(eng, reqs_of(stop), before_run)))
    return out


def _assert_same_run(got, want):
    assert got["tokens"] == want["tokens"]
    assert got["times"] == want["times"]
    assert got["spans"] == want["spans"]
    assert got["steps"] == want["steps"]
    assert got["tape"] == want["tape"]
    assert got["clock"] == want["clock"]


def test_greedy_streams_equal_as_widths_shrink(tiny):
    """Four requests finishing one after another: the step narrows 4, 2, 1,
    the lookahead runs most steps ahead, and the run is the reference's."""
    widths = {}

    def make():
        eng = _engine(tiny)
        widths[id(eng)] = _spy_widths(eng)
        return eng
    (eng, got), (ref, want) = _both(make, lambda stop: _requests(4, stop))
    _assert_same_run(got, want)
    assert widths[id(eng)] == widths[id(ref)]
    assert sorted(set(widths[id(eng)]), reverse=True) == [4, 2, 1]
    assert eng.stats()["lookahead_steps"] > 0
    assert ref.stats()["lookahead_steps"] == 0


def test_no_tick_lands_two_steps(tiny):
    """Each tick hands every request that was running before it at most one
    token, so a client sees tokens at the pace of the steps; the ticks
    that only land the step in flight come before the steps that end a
    row."""
    eng = _engine(tiny)
    for r in _requests(4, -1):
        eng.submit(r)
    reqs = list(eng.queue)
    landing_only = 0
    while eng.queue or eng.active:
        running = {r.request_id: len(r.output_tokens)
                   for r in eng.active.values()}
        steps = len(eng.trace) + (eng._flight is not None)
        eng.step()
        landing_only += len(eng.trace) + (eng._flight is not None) == steps
        for r in reqs:
            if r.request_id in running:
                assert len(r.output_tokens) - running[r.request_id] <= 1
    assert landing_only == len(reqs)
    assert eng.stats()["lookahead_steps"] > 0


def test_an_admission_that_can_end_lands_the_step_in_flight_first(tiny):
    """A request whose first step can end it (a stop token) arrives while
    three others run ahead: the tick before its admission only lands the
    step in flight, so the running requests still get one token a tick,
    and every stream is the reference's."""
    def serve(stop):
        eng = _engine(tiny)
        reqs = _requests(3, stop, base=12)
        for r in reqs:
            eng.submit(r)
        eng.step()
        eng.step()
        late = Request("late", prompt=[3, 4],
                       sampling=SamplingParams(max_new_tokens=5,
                                               stop_token=NEVER))
        eng.submit(late)
        flying = eng._flight is not None
        while eng.queue or eng.active:
            running = {r.request_id: len(r.output_tokens)
                       for r in eng.active.values()}
            eng.step()
            for r in reqs + [late]:
                if r.request_id in running:
                    assert len(r.output_tokens) - running[r.request_id] <= 1
        eng.close()
        return flying, {r.request_id: list(r.output_tokens)
                        for r in reqs + [late]}
    got, want = serve(-1), serve(NEVER)
    assert got[0] and not want[0]
    assert got[1] == want[1]


def test_temperature_top_k_streams_keep_the_key_order(tiny):
    """Sampled tokens depend on every key split, prompts' and steps'
    alike, in order: the streams are the reference's."""
    (eng, got), (_, want) = _both(
        lambda: _engine(tiny),
        lambda stop: _requests(4, stop, temperature=0.9, top_k=7))
    _assert_same_run(got, want)
    assert eng.stats()["lookahead_steps"] > 0
    # sampled, not greedy: some stream leaves its argmax path
    greedy = _serve(_engine(tiny), _requests(4, -1))
    assert got["tokens"] != greedy["tokens"]


@pytest.mark.parametrize("params", [
    SamplingParams(),
    SamplingParams(temperature=0.7),
    SamplingParams(temperature=1.0, top_k=20),
])
def test_first_rows_draw_as_a_narrower_batch(params):
    """The program samples every row of its executed width and the host
    keeps the first ``n``: those are the draws of an ``n``-row batch with
    the same key (threefry is partitionable), so no program is keyed on
    ``n``."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (8, 1, 300))
    key = jax.random.PRNGKey(3)
    wide = jax.jit(sample, static_argnums=2)(logits, key, params)
    for n in range(1, 8):
        np.testing.assert_array_equal(np.asarray(wide[:n]),
                                      np.asarray(sample(logits[:n], key,
                                                        params)))


def test_moe_streams_and_counts_equal():
    cfg = dataclasses.replace(
        smoke_config(get_config("deepseek-v2-lite-16b")), moe_top_k=6,
        n_shared_experts=2)
    model = Model(cfg)
    (eng, got), (ref, want) = _both(lambda: _engine(model),
                                    lambda stop: _requests(3, stop))
    _assert_same_run(got, want)
    assert eng.stats()["lookahead_steps"] > 0
    counts = eng.stats()["routed_assignments"]
    assert counts is not None and counts == ref.stats()["routed_assignments"]


def _late_restore(eng):
    """Start a pipelined restore for r0 after one step, so its slot sits
    steps out while the others decode."""
    eng.step()
    mgr = OffloadManager(eng.gateway, OffloadPolicy.REUSE_AWARE,
                         pipelined_restore=True, restore_chunk_bytes=8 << 10)
    for b in range(96):
        mgr.host_store[b] = HostBlock(b, 128 << 10, 2, None)
    mgr.on_restore_done.append(eng.mark_restore)
    mgr.restore(list(range(96)), key="r0")


def test_slots_deferred_by_a_pending_restore(tiny):
    def make():
        eng = _engine(tiny, policy=SP.SYNC_DRAIN, compute="qwen3p6-27b")
        eng.gateway.pool.prewarm()
        return eng
    (eng, got), (ref, want) = _both(
        make, lambda stop: _requests(4, stop, base=4, step=4),
        before_run=_late_restore)
    _assert_same_run(got, want)
    deferred = eng.stats()["overlap"]["deferred_slots"]
    assert deferred > 0 == ref.stats()["lookahead_steps"]
    assert deferred == ref.stats()["overlap"]["deferred_slots"]
    assert eng.stats()["lookahead_steps"] > 0


def test_the_ladders_forced_full_width(tiny):
    """The last rung runs three rows at the full width of an eight-slot
    engine; the pad rows read the resident vector as their row does."""
    widths = {}

    def make():
        eng = _engine(tiny, max_batch=8)
        ladder = DegradationLadder(recovery_quiet_s=float("inf"))
        FaultInjector(FaultPlan(seed=0), ladder=ladder).attach(eng.gateway)
        ladder.observe_fault(eng.clock.now)
        for _ in range(3):
            ladder.escalate(eng.clock.now)
        assert ladder.dense_step_forced
        widths[id(eng)] = _spy_widths(eng)
        return eng
    (eng, got), (ref, want) = _both(make, lambda stop: _requests(3, stop))
    _assert_same_run(got, want)
    assert set(widths[id(eng)]) == {8} and widths[id(eng)] == widths[id(ref)]
    assert eng.stats()["lookahead_steps"] > 0


def test_preemption_and_close_while_a_step_is_in_flight(tiny):
    """A preemption between ticks lands the step in flight before it
    requeues the request, and ``close()`` lands the last one: the partial
    streams, the preempted request's rerun and the tape are the
    reference's."""
    def serve(stop):
        eng = _engine(tiny)
        reqs = _requests(4, stop, base=12, step=2)
        for r in reqs:
            eng.submit(r)
        with TraceRecorder(eng.gateway, label="preempt") as rec:
            for _ in range(4):
                eng.step()
            flying = eng._flight is not None
            newest = max(eng.active.values(), key=lambda r: r.request_id)
            eng._release(newest, state="preempted")
            assert eng._flight is None
            for _ in range(5):
                eng.step()
            flying_at_close = eng._flight is not None
            eng.close()
        assert eng._flight is None
        # every token each running request decoded is on the host
        assert all(len(r.output_tokens) == 1 + r.decode_steps
                   for r in eng.active.values())
        return (flying, flying_at_close,
                {r.request_id: (list(r.output_tokens), r.restarts, r.state)
                 for r in reqs},
                json.dumps([r.to_dict() for r in rec.tape().records]))
    got, want = serve(-1), serve(NEVER)
    assert got[:2] == (True, True) and want[:2] == (False, False)
    assert got[2:] == want[2:]
    assert got[2]["r3"][1] == 1


def test_lookahead_steps_count():
    """A closed loop with long outputs runs at least 90 % of its steps
    ahead; with a stop token on every row, none."""
    model = smoke_model()
    counts = {}
    for stop in (-1, NEVER):
        eng = _engine(model, max_len=128)
        stats = _serve(eng, _requests(8, stop, base=64, step=1))
        counts[stop] = (eng.stats()["lookahead_steps"], len(stats["steps"]))
    ahead, steps = counts[-1]
    assert steps > 100 and ahead >= 0.9 * steps
    assert counts[NEVER] == (0, steps)
