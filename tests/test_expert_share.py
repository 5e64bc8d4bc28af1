"""DeepSeek-V2-Lite's block as served on one chip of an expert-parallel
group: an MoE layer that routes over all its experts and computes the part
of the result its held experts give, gates as published (softmax
probabilities, not renormalised), prompts routed without dropping a token,
YaRN rotary frequencies and softmax scale, and the assignment counts the
serving programs hand back on the token drain.  A model without experts
serves exactly as before."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.models.layers import (Param, init_moe, mla_softmax_scale,
                                 mlp_block, moe_block, pvalue, rope_table,
                                 yarn_rope)
from repro.models.model import Model
from repro.obs import wall
from repro.serving.engine import Request, ServingEngine, packed_step_of
from repro.serving.sampler import SamplingParams

F32 = jnp.float32


def _small(**overrides):
    """V2-Lite's block at 128 wide: a dense layer, then two MoE layers of 8
    routed experts, top-6, 2 shared; float32 so that shares add exactly."""
    cfg = smoke_config(get_config("deepseek-v2-lite-16b"))
    return dataclasses.replace(cfg, moe_top_k=6, n_shared_experts=2,
                               dtype=F32, logits_dtype=F32, **overrides)


def _held(p, cfg, offset: int, n: int):
    """The MoE params of a chip holding experts [offset, offset + n)."""
    p = dict(p)
    for k in ("wi", "wg", "wo"):
        p[k] = Param(pvalue(p[k])[offset:offset + n], p[k].axes)
    return p, dataclasses.replace(cfg, n_experts_held=n, expert_offset=offset)


def _moe(p, x, cfg):
    with jax.default_matmul_precision("highest"):
        return moe_block(p, x, cfg, no_drop=True)


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """Two chips holding experts 0-3 and 4-7: their parts, with the shared
    experts (which every chip computes alike) counted once, give the uncut
    layer; so do their assignment counts."""
    cfg = _small()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model), F32)
    y_all, _, n_all = _moe(p, x, cfg)
    with jax.default_matmul_precision("highest"):
        shared = mlp_block(p["shared"], x, cfg)
    parts, counts = [], []
    for offset in (0, 4):
        ph, ch = _held(p, cfg, offset, 4)
        assert pvalue(ph["router"]).shape == (cfg.d_model, 8)
        y, _, n = _moe(ph, x, ch)
        parts.append(y - shared)
        counts.append(np.asarray(n))
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(y_all), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(n_all))
    assert int(n_all.sum()) == 2 * 9 * cfg.moe_top_k


def test_init_holds_only_the_held_experts():
    cfg = _small(n_experts_held=4, expert_offset=4)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    assert pvalue(p["router"]).shape == (cfg.d_model, 8)
    assert pvalue(p["wi"]).shape == (4, cfg.d_model, cfg.d_expert)
    assert pvalue(p["wo"]).shape == (4, cfg.d_expert, cfg.d_model)


def test_gates_are_the_softmax_probabilities_not_renormalised():
    """The routed part is each held top-k expert's output times its full
    softmax probability; with ``norm_topk_prob`` it is rescaled, and differs."""
    cfg = _small(n_experts_held=4, expert_offset=4)
    p = init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 7, cfg.d_model), F32)
    y, _, _ = _moe(p, x, cfg)
    xt = np.asarray(x[0], np.float64)
    logits = xt @ np.asarray(pvalue(p["router"]), np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1)[:, :cfg.moe_top_k]
    silu = lambda a: a / (1 + np.exp(-a))
    w = {k: np.asarray(pvalue(p[k]), np.float64) for k in ("wi", "wg", "wo")}
    want = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        for e in top[t]:
            if 4 <= e < 8:
                j = e - 4
                h = silu(xt[t] @ w["wg"][j]) * (xt[t] @ w["wi"][j])
                want[t] += probs[t, e] * (h @ w["wo"][j])
    sh = {k: np.asarray(pvalue(p["shared"][k]), np.float64)
          for k in ("wi", "wg", "wo")}
    want += (silu(xt @ sh["wg"]) * (xt @ sh["wi"])) @ sh["wo"]
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5, rtol=1e-5)
    assert probs[np.arange(7)[:, None], top].sum(-1).max() < 0.99
    y_norm, _, _ = _moe(p, x, dataclasses.replace(cfg, norm_topk_prob=True))
    assert float(jnp.max(jnp.abs(y_norm - y))) > 1e-2


def test_prefill_drops_no_token():
    """With a router that sends every token to the same experts, a capacity
    buffer would drop most of them: prefill routes them all (its counts say
    so) and its logits are those of the drop-free forward."""
    cfg = _small(n_experts_held=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    blocks = params["blocks"]
    router = blocks["mlp"]["router"]
    blocks["mlp"]["router"] = Param(jnp.zeros_like(pvalue(router)),
                                    router.axes)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                                cfg.vocab_size)
    logits, _, _, routed = model.prefill(params, {"tokens": tokens}, 32,
                                         routed=True)
    # equal probabilities: every token takes experts 0..k-1
    want = np.zeros((cfg.n_layers - 1, 8), np.int32)
    want[:, :cfg.moe_top_k] = 24
    np.testing.assert_array_equal(np.asarray(routed), want)
    free, _ = Model(dataclasses.replace(cfg, capacity_factor=16.0)).forward(
        params, {"tokens": tokens})
    dropping, _ = model.forward(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits[0, 0]),
                               np.asarray(free[0, -1]), atol=1e-4)
    assert float(jnp.max(jnp.abs(dropping[0, -1] - free[0, -1]))) > 1e-2


def test_yarn_frequencies_and_softmax_scale_as_published():
    """V2-Lite's 64 rope dims at factor 40 over 4096 positions: the pairs
    below the fast correction dim 10 keep ``10000^(-2i/64)``, those from the
    slow one 23 on are divided by 40, a linear ramp blends between; cos and
    sin are not rescaled (mscale / mscale_all_dim = 1); the softmax scale is
    ``192^-0.5 (0.1 x 0.707 x ln 40 + 1)^2``."""
    cfg = get_config("deepseek-v2-lite-16b")
    inv_freq, mscale = yarn_rope(cfg, 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(np.asarray(inv_freq),
                               extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    assert mscale == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert mla_softmax_scale(cfg) == pytest.approx(0.0722 * 1.590, rel=1e-3)
    # without YaRN: plain frequencies and 1/sqrt(192)
    plain = dataclasses.replace(cfg, yarn_factor=0.0)
    assert yarn_rope(plain, 64) == (None, 1.0)
    assert mla_softmax_scale(plain) == 1 / math.sqrt(192)
    pos = jnp.arange(5)
    plain_freq = 1.0 / (10000.0 ** (jnp.arange(0, 32, dtype=F32) / 32))
    np.testing.assert_array_equal(
        np.asarray(rope_table(pos, 64)[0]),
        np.asarray(jnp.sin(pos.astype(F32)[:, None] * plain_freq)))


def _requests(n, vocab, max_new=8):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", prompt=rng.integers(1, vocab, 5 + 4 * i).tolist(),
                    sampling=SamplingParams(max_new_tokens=max_new))
            for i in range(n)]


def _serve(model, reqs):
    eng = ServingEngine(model, max_batch=4, max_len=64, seed=3)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng


def test_engine_carries_the_counts_on_the_token_drain(monkeypatch):
    """With every expert held, each prompt token and each executed decode
    row (a bucket's pad row too) routes ``top_k`` times in every MoE layer:
    the engine's running total says so, each packed step's drain carries
    its tokens and the counts, and an ``engine.routed`` span holds each
    program's counts."""
    monkeypatch.setenv("REPRO_OBS", "1")
    wall.refresh()
    wall.clear()
    try:
        cfg = _small(n_experts_held=8)
        reqs = _requests(3, cfg.vocab_size)
        eng = _serve(Model(cfg), reqs)
        spans = [r for r in wall.records() if r.name == "engine.routed"]
    finally:
        monkeypatch.undo()
        wall.refresh()
        wall.clear()
    layers = cfg.n_layers - 1
    rows = sum(eng._bucket(s.packed) for s in eng.trace)
    prompt = sum(len(r.prompt) for r in reqs)
    total = np.asarray(eng.stats()["routed_assignments"])
    assert total.shape == (layers, 8)
    assert total.sum() == (prompt + rows) * cfg.moe_top_k * layers
    assert len(spans) == len(reqs) + len(eng.trace)
    np.testing.assert_array_equal(sum(np.asarray(s.attrs["counts"])
                                      for s in spans), total)
    for s in eng.trace:
        assert s.drain_bytes == 4 * (s.packed + layers * 8)
    assert all(len(r.output_tokens) == 8 for r in eng.finished)


def test_a_model_without_experts_serves_as_before():
    """olmo's packed step hands back the logits and cache of the plain
    decode step, and its drain is its sampled tokens alone."""
    model = Model(smoke_config(get_config("olmo-1b")))
    params = model.init(jax.random.PRNGKey(0))
    caches = model.init_cache(4, 32)
    rows = np.arange(2, dtype=np.int32)
    tokens = np.ones((2, 1), np.int32)
    want, want_cache = model.decode_step(params, jax.tree.map(jnp.copy, caches),
                                         tokens, rows + 3, rows)
    got, got_cache, drain, *_ = packed_step_of(model)(
        params, caches, jnp.zeros(4, jnp.int32), jax.random.PRNGKey(0),
        tokens, rows + 3, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(drain), np.argmax(np.asarray(want, np.float32)[:, -1], -1))
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng = _serve(model, _requests(3, model.cfg.vocab_size))
    assert eng.stats()["routed_assignments"] is None
    assert all(s.drain_bytes == 4 * s.packed for s in eng.trace)


def test_a_dropped_engine_frees_its_weights():
    """An engine under the worker-drain policy, dropped without ``close()``
    (as a benchmark drops it before its reference check), is collected: its
    weights and cache are freed."""
    import gc
    import weakref
    from repro.core.policy import SchedulingPolicy
    model = Model(smoke_config(get_config("olmo-1b")))
    eng = ServingEngine(model, max_batch=2, max_len=32, seed=0,
                        policy=SchedulingPolicy.WORKER_DRAIN)
    eng.submit(_requests(1, model.cfg.vocab_size, max_new=3)[0])
    eng.run()
    alive = weakref.ref(eng)
    del eng
    gc.collect()
    assert alive() is None
