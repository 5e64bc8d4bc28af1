"""Wall-clock spans (``repro.obs.wall``, DESIGN.md §9): the recorder, and
the span tree one engine tick leaves under the serving defaults a CC
deployment runs (``cc_on=True``: the worker drain, batched prep).

The laws pinned here:

  * spans nest by thread: a parent is the span open around it on the same
    thread, or the one a working thread adopts with ``under``;
  * the ring keeps the newest ``CAPACITY`` records and says from when on it
    holds every span; ``clear()`` forgets them; under ``REPRO_OBS=0`` a span
    is a shared no-op that stamps nothing;
  * a decode-only tick is one ``sched.tick`` holding the admission, the
    dispatch, the sampling, the drain (its wait and copy on the worker
    thread) and the consumption, and it makes exactly two real crossings:
    the step's tokens, positions and slots in one ``[3, width]`` upload,
    and one drain (the modelled prep is priced, and nothing moves); an
    admission makes two: the prompt through the gateway, which the prefill
    reads, and the first token;
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
        assert wall.current() == span.id
        span.end(250, stepped=3)
    assert wall.current() == 0
    (rec,) = wall.records()
    assert (rec.start_ns, rec.end_ns, rec.attrs) == (100, 250, {"stepped": 3})


def test_parents_are_per_thread_and_adoptable(recorder):
    seen = {}

    def work(parent):
        with wall.span("alone"):
            pass
        with wall.under(parent):
            with wall.span("adopted"):
                pass
        seen["thread"] = threading.get_ident()

    with wall.span("main"):
        t = threading.Thread(target=work, args=(wall.current(),))
        t.start()
        t.join()
    by = {r.name: r for r in wall.records()}
    assert by["alone"].parent == 0
    assert by["adopted"].parent == by["main"].id
    assert by["adopted"].thread == seen["thread"] != by["main"].thread


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
        assert wall.current() == 0
    with wall.under(7):
        assert wall.current() == 0
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
    for t in decode_only:
        top = [r.name for r in sorted(kids[t.id], key=lambda r: r.start_ns)]
        assert [n for n in top if n.startswith("engine.")] == [
            "engine.admit", "engine.dispatch", "engine.sample",
            "engine.drain", "engine.consume"]
        drain = next(r for r in kids[t.id] if r.name == "engine.drain")
        # the worker thread waits for the tokens and copies them, below
        # the drain that handed them over
        worker = {r.name: r for r in kids[drain.id]}
        assert {"bridge.wait", "bridge.d2h"} <= set(worker)
        assert worker["bridge.d2h"].thread != main
        assert worker["bridge.d2h"].attrs["op_class"] == "worker_drain"
        below = _below(kids, t)
        dispatch = next(r for r in kids[t.id] if r.name == "engine.dispatch")
        ups = [r for r in below if r.name == "bridge.h2d"]
        assert [(r.attrs["op_class"], r.attrs["nbytes"], r.parent)
                for r in ups] == [
            ("decode_input", 12 * dispatch.attrs["width"], dispatch.id)]
        assert sum(r.name in CROSSINGS for r in below) == 2
        assert any(r.name == "account" for r in below)
        assert all(t.start_ns <= r.start_ns <= r.end_ns <= t.end_ns
                   for r in below)


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
