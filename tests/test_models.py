"""Per-arch smoke tests (reduced configs, CPU) + prefill/decode consistency.

Assignment requirement: for each architecture, a REDUCED same-family config
runs one forward/train step on CPU asserting output shapes + no NaNs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, SHAPES, all_configs, get_config, smoke_config, shape_applicable
from repro.models.model import Model

KEY = jax.random.PRNGKey(0)
CONFIGS = all_configs()


def make_batch(sc, B=2, S=10):
    toks = jax.random.randint(KEY, (B, S), 0, sc.vocab_size)
    batch = {"tokens": toks, "targets": toks,
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    if sc.family == "vlm":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            KEY, (B, sc.frontend_tokens, sc.d_model)).astype(sc.dtype)
    if sc.encoder_layers:
        batch["frames"] = 0.02 * jax.random.normal(
            KEY, (B, sc.frontend_tokens, sc.d_model)).astype(sc.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        sc = smoke_config(CONFIGS[arch])
        m = Model(sc)
        params = m.init(KEY)
        B, S = 2, 10
        batch = make_batch(sc, B, S)
        logits, aux = m.forward(params, batch)
        assert logits.shape == (B, S, sc.vocab_size)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
        loss, metrics = m.loss(params, batch)
        assert np.isfinite(float(loss))

    def test_one_train_step(self, arch):
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_loop import init_train_state, make_train_step
        sc = smoke_config(CONFIGS[arch])
        m = Model(sc)
        params = m.init(KEY)
        oc = AdamWConfig(lr=1e-3, warmup_steps=1)
        state = init_train_state(params, oc)
        step = make_train_step(sc, oc)
        p2, s2, metrics = step(params, state, make_batch(sc))
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    """Teacher-forced decode must match the parallel forward.  MoE archs are
    compared under no-drop capacity (drop policy differs by step size); MLA
    tolerates the absorbed-vs-decompressed bf16 difference."""
    cfg = CONFIGS[arch]
    sc = smoke_config(cfg)
    if cfg.n_routed_experts:
        sc = dataclasses.replace(sc, capacity_factor=16.0)
    m = Model(sc)
    params = m.init(KEY)
    B, S, P = 2, 10, 6
    batch = make_batch(sc, B, S)
    full_logits, _ = m.forward(params, batch)
    logits, caches, idx0 = m.prefill(
        params, dict(batch, tokens=batch["tokens"][:, :P]), max_len=32)
    tol = 0.12 if cfg.use_mla else 0.02
    errs = [float(jnp.max(jnp.abs(
        logits[:, 0].astype(jnp.float32) - full_logits[:, P - 1].astype(jnp.float32))))]
    index = idx0
    for t in range(P, S):
        sl, caches = m.decode_step(params, caches, batch["tokens"][:, t:t + 1], index)
        errs.append(float(jnp.max(jnp.abs(
            sl[:, 0].astype(jnp.float32) - full_logits[:, t].astype(jnp.float32)))))
        index += 1
    assert max(errs) < tol, f"{arch}: decode/forward divergence {max(errs)}"


def test_exact_assigned_configs():
    """The full configs carry the exact assigned hyperparameters."""
    spec = {
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "nemotron-4-340b": (96, 18432, 96, 8, 73728, 256000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        c = CONFIGS[arch]
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, d, h, kv, ff, v), arch
    assert CONFIGS["deepseek-v2-lite-16b"].kv_lora_rank == 512
    assert CONFIGS["deepseek-moe-16b"].moe_top_k == 6
    assert CONFIGS["deepseek-moe-16b"].n_shared_experts == 2
    assert CONFIGS["hymba-1.5b"].ssm_state == 16


def test_long_context_applicability():
    """long_500k runs for SSM/hybrid, skips for pure full attention."""
    long = SHAPES["long_500k"]
    runnable = {a for a in ARCH_IDS if a != "qwen3p6-27b"
                and shape_applicable(CONFIGS[a], long)[0]}
    assert runnable == {"xlstm-1.3b", "hymba-1.5b"}


def test_moe_load_balance_aux_positive():
    sc = smoke_config(CONFIGS["deepseek-moe-16b"])
    m = Model(sc)
    params = m.init(KEY)
    _, aux = m.forward(params, make_batch(sc))
    assert float(aux) >= 1.0  # Switch aux >= 1 (==1 at perfect balance)
