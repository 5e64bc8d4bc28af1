"""Wall-clock spans (``repro.obs.wall``, DESIGN.md §9): the recorder, and
the span tree one engine tick leaves under the serving defaults a CC
deployment runs (``cc_on=True``: the worker-priced drain, batched prep).

The laws pinned here:

  * spans nest by thread: a parent is the span open around it on the same
    thread;
  * the ring keeps the newest ``CAPACITY`` records and says from when on it
    holds every span; ``clear()`` forgets them; under ``REPRO_OBS=0`` a span
    is a shared no-op that stamps nothing;
  * a decode-only tick is one ``sched.tick`` holding the admission, the
    dispatch of its step (which samples inside the program), then the
    drain (its wait and copy, on the engine's thread) and consumption of
    the step in flight before it and, where its own step can end a row, of
    its own; before a step that can end a row, a tick only lands the step
    in flight.  A tick whose step follows one in flight and stays in flight
    makes exactly two real crossings: the step's tokens, positions and
    slots in one ``[3, width]`` upload, and the drain of the step before
    (the modelled prep is priced, and nothing moves); an admission makes
    two: the prompt through the gateway, which the prefill reads, and the
    first token;
  * the instrument is passive: token streams and the bridge tape are equal
    with the recorder on and off.
"""

import dataclasses
import threading
from collections import defaultdict

import pytest

from repro.core.policy import cc_aware_defaults
from repro.obs import Observatory, wall
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import Scheduler
from repro.trace import TraceRecorder
from repro.trace.harness import smoke_model


@pytest.fixture
def recorder(monkeypatch):
    """The recorder switched on and empty; restored after the test."""
    monkeypatch.setenv("REPRO_OBS", "1")
    wall.refresh()
    wall.clear()
    yield wall
    monkeypatch.undo()
    wall.refresh()
    wall.clear()


# -- the recorder ------------------------------------------------------------


def test_spans_nest_with_parents_and_attrs(recorder):
    with wall.span("outer", what="a") as outer:
        with wall.span("inner"):
            pass
        outer.end(outer.start_ns + 10**9, more=1)
    with wall.span("second"):
        pass
    by = {r.name: r for r in wall.records()}
    assert [r.name for r in wall.records()] == ["inner", "outer", "second"]
    assert by["outer"].parent == 0 and by["second"].parent == 0
    assert by["inner"].parent == by["outer"].id
    assert by["outer"].attrs == {"what": "a", "more": 1}
    assert (by["outer"].start_ns <= by["inner"].start_ns
            <= by["inner"].end_ns <= by["outer"].end_ns)
    assert by["outer"].end_ns - by["outer"].start_ns == 10**9
    assert by["outer"].thread == threading.get_ident()


def test_given_stamps_and_current(recorder):
    with wall.span("tick", start_ns=100) as span:
        with wall.span("inner"):
            pass
        span.end(250, stepped=3)
    inner, tick = wall.records()
    assert inner.parent == tick.id == span.id
    assert (tick.start_ns, tick.end_ns, tick.attrs) == (100, 250,
                                                        {"stepped": 3})


def test_parents_are_per_thread_and_adoptable(recorder):
    """A span opened on another thread, while a span is open on this one,
    is a root there: parents never cross threads."""
    seen = {}

    def work():
        with wall.span("alone"):
            with wall.span("nested"):
                pass
        seen["thread"] = threading.get_ident()

    with wall.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    by = {r.name: r for r in wall.records()}
    assert by["alone"].parent == 0
    assert by["nested"].parent == by["alone"].id
    assert by["alone"].thread == seen["thread"] != by["main"].thread


def test_ring_is_bounded_and_says_what_it_let_go(recorder):
    assert wall.since_ns() == 0
    for _ in range(wall.CAPACITY + 5):
        with wall.span("s"):
            pass
    recs = wall.records()
    assert len(recs) == wall.CAPACITY
    # the five oldest went; every span that started after them is held
    assert 0 < wall.since_ns() <= recs[0].start_ns
    wall.clear()
    assert wall.records() == [] and wall.since_ns() == 0


def test_off_is_a_shared_noop(monkeypatch, recorder):
    monkeypatch.setenv("REPRO_OBS", "0")
    wall.refresh()
    a, b = wall.span("x", k=1), wall.span("y")
    assert a is b
    with a as s:
        s.end(5, k=2)
        with wall.span("z"):
            pass
    assert wall.records() == []


# -- one engine, under the serving defaults ------------------------------------


def _serve(n_requests=4, max_batch=4, obs=None):
    """A smoke engine built as the benchmark builds one (``cc_on=True``),
    served to completion; returns (engine, its tape, token streams)."""
    defaults = dataclasses.replace(cc_aware_defaults(True), observability=True)
    engine = ServingEngine(smoke_model(), max_batch=max_batch, max_len=64,
                           cc_on=True, defaults=defaults, obs=obs, seed=3)
    sched = Scheduler(engine)
    for i in range(n_requests):
        sched.submit(Request(
            f"r{i}", prompt=list(range(1, 9 + 2 * i)),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=5 + i)))
    with TraceRecorder(engine.gateway, label="wall") as rec:
        while engine.queue or engine.active:
            sched.tick()
    engine.close()
    return engine, rec.tape(), {r.request_id: list(r.output_tokens)
                                for r in engine.finished}


def _tree(recs):
    kids = defaultdict(list)
    for r in recs:
        kids[r.parent].append(r)
    return kids


def _below(kids, rec):
    out, todo = [], list(kids[rec.id])
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(kids[r.id])
    return out


CROSSINGS = {"bridge.h2d", "bridge.d2h"}


def test_decode_tick_span_tree_and_crossings(recorder):
    engine, _, _ = _serve()
    recs = wall.records()
    kids = _tree(recs)
    ticks = [r for r in recs if r.name == "sched.tick"]
    assert ticks and all(t.parent == 0 for t in ticks)
    assert engine.policy.value == "worker"
    decode_only = [t for t in ticks if t.attrs["stepped"] > 0
                   and t.attrs["admitted"] == 0 and not t.attrs["compiled"]]
    assert decode_only
    main = threading.get_ident()
    ahead = landing = 0
    for t in decode_only:
        top = [r for r in sorted(kids[t.id], key=lambda r: r.start_ns)
               if r.name.startswith("engine.")]
        below = _below(kids, t)
        assert "engine.sample" not in {r.name for r in below}
        if top[0].name == "engine.drain":
            # the next step can end a row: this tick lands the step in
            # flight alone, with its one crossing
            assert [r.name for r in top] == ["engine.drain",
                                             "engine.consume"]
            assert sum(r.name in CROSSINGS for r in below) == 1
            landing += 1
            continue
        dispatch = top[1]
        inflight = dispatch.attrs["inflight"]
        # the step goes first, then the step in flight lands, then this
        # one unless it stays in flight
        drains = (len(top) - 2) // 2
        assert drains in (inflight, inflight + 1)
        assert [r.name for r in top] == [
            "engine.admit", "engine.dispatch"] + [
            "engine.drain", "engine.consume"] * drains
        # the engine's thread waits for the tokens and copies them, below
        # each drain, priced as a worker-channel drain
        for drain in (r for r in top if r.name == "engine.drain"):
            copy = {r.name: r for r in kids[drain.id]}
            assert {"bridge.wait", "bridge.d2h"} <= set(copy)
            assert copy["bridge.d2h"].attrs["op_class"] == "worker_drain"
        assert all(r.thread == main for r in below)
        ups = [r for r in below if r.name == "bridge.h2d"]
        assert [(r.attrs["op_class"], r.attrs["nbytes"], r.parent)
                for r in ups] == [
            ("decode_input", 12 * dispatch.attrs["width"], dispatch.id)]
        crossings = sum(r.name in CROSSINGS for r in below)
        assert crossings == 1 + drains
        if inflight and drains == 1:
            ahead += 1
            assert crossings == 2
        assert any(r.name == "account" for r in below)
        assert all(t.start_ns <= r.start_ns <= r.end_ns <= t.end_ns
                   for r in below)
    assert ahead and landing
    # every step drains once, and the dispatches that found a step in
    # flight are the engine's count
    assert sum(r.name == "engine.drain" for r in recs) == len(engine.trace)
    assert sum(r.attrs["inflight"] for r in recs
               if r.name == "engine.dispatch") == \
        engine.stats()["lookahead_steps"] > 0


def test_admission_crossings(recorder):
    engine, _, _ = _serve()
    recs = wall.records()
    kids = _tree(recs)
    prefills = [r for r in recs if r.name == "engine.prefill"]
    assert len(prefills) == 4
    for p in prefills:
        below = _below(kids, p)
        assert sorted(r.attrs["op_class"] for r in below
                      if r.name in CROSSINGS) == [
            "prompt_h2d", "sample_d2h"]
        assert {"engine.insert", "engine.sample"} <= {r.name for r in below}
        assert p.attrs["prompt_len"] == len(next(
            r for r in engine.finished if r.request_id == p.attrs["req"]
        ).prompt)
    admitted = sum(t.attrs["admitted"] for t in recs
                   if t.name == "sched.tick")
    assert admitted == 4


def test_replica_label_rides_the_tick(recorder):
    _serve(n_requests=1, obs=Observatory(replica="r7", tenant="t0"))
    ticks = [r for r in wall.records() if r.name == "sched.tick"]
    assert ticks and all(t.attrs["replica"] == "r7" for t in ticks)


def test_tokens_and_tape_equal_with_the_recorder_on_and_off(monkeypatch,
                                                           recorder):
    _, tape_on, tokens_on = _serve()
    assert wall.records()
    monkeypatch.setenv("REPRO_OBS", "0")
    wall.refresh()
    wall.clear()
    _, tape_off, tokens_off = _serve()
    assert wall.records() == []
    assert tokens_on == tokens_off
    assert tape_on.to_json() == tape_off.to_json()
