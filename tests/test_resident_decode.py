"""The packed step decodes in place on the resident cache (DESIGN.md §10).

Against the composition it replaced — copy the packed rows out of the
resident cache (``gather_slot_cache``), a plain ``decode_step`` over them,
copy them back (``scatter_slot_cache``) — it gives the same logits and the
same cache, bit for bit, step after step: at full and partial width, with
bucket padding, stacked and per-layer caches, a ring cache past its window,
latent (MLA) caches, a dense first block, recurrent state and read-only
cross-attention K/V.  The engine counts the packed steps that ran it, and
the ladder's last rung runs the same step at its full width.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core.bridge import B300, BridgeModel
from repro.core.policy import SchedulingPolicy as SP, cc_aware_defaults
from repro.models.model import Model
from repro.resilience import DegradationLadder, FaultInjector, FaultPlan
from repro.serving.engine import Request, ServingEngine, packed_step_of
from repro.serving.kv_cache import gather_slot_cache, scatter_slot_cache
from repro.serving.sampler import SamplingParams
from repro.trace import TraceRecorder
from repro.trace import opclasses as oc

MAX_BATCH = 8
STEPS = 6


def _smoke(arch, **overrides):
    return Model(dataclasses.replace(smoke_config(get_config(arch)),
                                     **overrides))


def _copy_step(model):
    """The packed step as it was: gather, decode the packed rows, scatter."""
    scan = model.cfg.scan_layers

    def step(params, caches, tokens, index, slots):
        packed = gather_slot_cache(caches, slots, scan_layers=scan)
        logits, packed = model.decode_step(params, packed, tokens, index)
        return logits, scatter_slot_cache(caches, packed, slots,
                                          scan_layers=scan)
    return jax.jit(step)


def _ring_pos(start: int, cap: int) -> np.ndarray:
    """The ``pos`` row of a slot holding positions ``[0, start)`` in a cache
    of ``cap`` entries: entry t holds the latest position p < start with
    p % cap == t, or -1."""
    pos = np.full(cap, -1, np.int32)
    for p in range(max(0, start - cap), start):
        pos[p % cap] = p
    return pos


def _filled_cache(model, max_len, enc_len, starts, rng):
    """A resident cache as prefill and earlier steps leave it: random K/V,
    latents and state; each slot's ``pos`` holding its first ``starts``
    positions."""
    caches = model.init_cache(MAX_BATCH, max_len, enc_len)

    def fill(path, leaf):
        name = jax.tree_util.keystr(path[-1:])
        if name == "['pos']":
            rows = np.stack([_ring_pos(s, leaf.shape[-1]) for s in starts])
            return jnp.asarray(np.broadcast_to(rows, leaf.shape))
        return jnp.asarray(0.5 * rng.standard_normal(leaf.shape), leaf.dtype)
    return jax.tree_util.tree_map_with_path(fill, caches)


def _assert_same(a, b, what):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}{jax.tree_util.keystr(path)}")


#: name -> (model, max_len, enc_len, start index range, rows each step
#: (slot ids; a repeat is a bucket pad row duplicating the first one))
CASES = {
    "dense-full-width": (lambda: _smoke("olmo-1b"), 64, 0, (3, 40),
                         list(range(8))),
    "dense-partial-width": (lambda: _smoke("olmo-1b"), 64, 0, (3, 40),
                            [6, 1]),
    "dense-bucket-pad": (lambda: _smoke("olmo-1b"), 64, 0, (3, 40),
                         [2, 5, 7, 2]),
    "dense-per-layer-cache": (lambda: _smoke("olmo-1b", scan_layers=False),
                              64, 0, (3, 40), [0, 3, 4, 0]),
    "hybrid-ring-past-window": (lambda: _smoke("hymba-1.5b"), 96, 0,
                                (58, 70), [1, 2, 4, 7, 1, 1, 1, 1]),
    "mla-moe": (lambda: _smoke("deepseek-v2-lite-16b"), 64, 0, (3, 40),
                [0, 2, 5, 0]),
    "moe-dense-block0": (lambda: _smoke("deepseek-moe-16b"), 64, 0, (3, 40),
                         [3, 6]),
    "xlstm-state": (lambda: _smoke("xlstm-1.3b"), 64, 0, (3, 40),
                    [4, 0, 7, 4]),
    "encdec-cross-kv": (lambda: _smoke("seamless-m4t-medium"), 64, 8,
                        (3, 40), [1, 6]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_packed_step_matches_the_copying_composition(case):
    make, max_len, enc_len, (lo, hi), rows = CASES[case]
    model = make()
    rng = np.random.default_rng(sorted(CASES).index(case))
    params = model.init(jax.random.PRNGKey(0))
    starts = rng.integers(lo, hi, MAX_BATCH)
    caches = _filled_cache(model, max_len, enc_len, starts, rng)
    old, new = _copy_step(model), packed_step_of(model)
    resident = jax.tree.map(jnp.copy, caches)
    last, key = jnp.zeros(MAX_BATCH, jnp.int32), jax.random.PRNGKey(0)
    slots = np.asarray(rows, np.int32)
    for step in range(STEPS):
        tokens = rng.integers(0, model.cfg.vocab_size, (len(rows), 1))
        index = starts[slots] + step
        # pad rows carry the token and index of the row they duplicate
        first = {s: i for i, s in reversed(list(enumerate(rows)))}
        dup = np.asarray([first[s] for s in rows])
        tokens, index = tokens[dup].astype(np.int32), index.astype(np.int32)
        want, caches = old(params, caches, tokens, index, slots)
        got, resident, _, last, key = new(params, resident, last, key,
                                          tokens, index, slots)
        assert np.isfinite(np.asarray(want, np.float32)).all()
        _assert_same(got, want, f"{case} step {step} logits")
        _assert_same(resident, caches, f"{case} step {step} cache")
    if case == "hybrid-ring-past-window":
        assert (starts[slots] + STEPS > model.cfg.sliding_window).any()


def test_packed_step_donates_the_resident_cache():
    model = _smoke("olmo-1b")
    params = model.init(jax.random.PRNGKey(0))
    caches = model.init_cache(MAX_BATCH, 32)
    rows = np.arange(2, dtype=np.int32)
    last = jnp.zeros(MAX_BATCH, jnp.int32)
    _, out, _, last_out, _ = packed_step_of(model)(
        params, caches, last, jax.random.PRNGKey(0),
        np.ones((2, 1), np.int32), rows, rows)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(caches))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(out))
    # the last-token vector is donated with it
    assert last.is_deleted() and not last_out.is_deleted()


def _engine(model, ladder=None, n_requests=4, max_batch=4):
    eng = ServingEngine(
        model, max_batch=max_batch, max_len=64, policy=SP.SYNC_DRAIN,
        bridge=BridgeModel(B300, cc_on=True),
        defaults=cc_aware_defaults(True, concurrency=4), seed=0)
    if ladder is not None:
        FaultInjector(FaultPlan(seed=0), ladder=ladder).attach(eng.gateway)
    for i in range(n_requests):
        eng.submit(Request(f"r{i}", prompt=[1, 2, 3 + i],
                           sampling=SamplingParams(max_new_tokens=4 + 2 * i)))
    return eng


def _spy_widths(eng) -> list:
    """Record the executed width of each of ``eng``'s packed steps."""
    step, widths = eng._packed_decode, []

    def spy(params, caches, tokens, index, slots):
        widths.append(len(slots))
        return step(params, caches, tokens, index, slots)
    eng._packed_decode = spy
    return widths


def _force_last_rung(ladder, now):
    ladder.observe_fault(now)
    for _ in range(3):
        ladder.escalate(now)
    assert ladder.dense_step_forced


def _serve(forced_steps):
    """Serve four requests in an eight-slot engine, the ladder forcing its
    last rung for ``forced_steps`` steps after the first; returns (engine,
    stats, tokens, executed widths)."""
    ladder = DegradationLadder(recovery_quiet_s=float("inf"))
    eng = _engine(_smoke("olmo-1b"), ladder, max_batch=8)
    widths = _spy_widths(eng)
    try:
        eng.step()
        if forced_steps:
            _force_last_rung(ladder, eng.clock.now)
            # an iteration that only lands the step in flight runs none
            while len(widths) < 1 + forced_steps:
                eng.step()
            ladder.recovery_quiet_s = 0.0   # one rung down a step from here
        stats = eng.run()
    finally:
        eng.close()
    tokens = {r.request_id: r.output_tokens for r in eng.finished}
    return eng, stats, tokens, widths


@pytest.mark.parametrize("dense_steps", [0, 3])
def test_resident_decode_steps_counts_packed_steps(dense_steps):
    """Every step runs the in-place path; the steps the ladder's last rung
    forces run it at the full width, with the same tokens."""
    eng, stats, tokens, widths = _serve(dense_steps)
    assert stats["resident_decode_steps"] == len(eng.trace) == len(widths)
    forced = widths[1:1 + dense_steps]
    assert forced == [eng.max_batch] * dense_steps
    assert max(widths[:1] + widths[1 + dense_steps:]) == 4 < eng.max_batch
    assert tokens == _serve(0)[2]


@pytest.mark.parametrize("forced_steps", [0, 3])
def test_every_step_is_packed(forced_steps):
    eng, _, _, _ = _serve(forced_steps)
    assert eng.trace and all(t.packed == t.active for t in eng.trace)


def test_forced_full_width_prices_the_real_rows():
    """At the last rung a three-row step runs at width 4, but its prep,
    its drain and its compute charge cover the three real rows."""
    ladder = DegradationLadder(recovery_quiet_s=float("inf"))
    eng = _engine(_smoke("olmo-1b"), ladder, n_requests=3)
    widths = _spy_widths(eng)
    try:
        _force_last_rung(ladder, eng.clock.now)
        eng._admit()
        kv = [float(r.index) for r in eng.active.values()]
        with TraceRecorder(eng.gateway, label="rung3") as rec:
            eng.step()
    finally:
        eng.close()
    (step,) = eng.trace
    assert widths == [4] and step.active == step.packed == 3
    assert step.prep_bytes == 6 * 3 * 4 and step.drain_bytes == 3 * 4
    (charge,) = [r for r in rec.tape().records
                 if r.op_class == oc.DECODE_PACKED]
    assert charge.duration_s == pytest.approx(
        eng.compute.decode_charge(kv).seconds, rel=1e-9)
    assert oc.DEGRADED in charge.tags
