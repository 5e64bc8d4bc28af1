"""The plain references against the program at a small size on the CPU:
the same weights from the same seed, and the same logits when the program
computes in float32."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testcell
from chipbench import harness, refcore

CONFIGS = chipbench_testcell.REPO / "chipbench" / "configs"
SMALL = {
    "olmo1b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                   head_dim=32, d_ff=256, vocab_size=512),
}


def _small(name, dtype="bfloat16"):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    config["model"].update(SMALL[name], attn_impl="dense", dtype=dtype,
                           logits_dtype=dtype)
    return config, harness._module(CONFIGS / f"{name}.py")


def _program_weights(params, cfg):
    """The program's weight tree as the reference lays it out."""
    blocks = params["blocks"]
    if not isinstance(blocks, list):
        blocks = [jax.tree.map(lambda a: a[i], blocks)
                  for i in range(cfg.n_layers)]
    val = lambda p: np.asarray(p.value, np.float32)
    out = []
    for b in blocks:
        layer = {k: val(b["attn"][k]) for k in ("wq", "wk", "wv", "wo")}
        layer.update(wi=val(b["mlp"]["wi"]), wg=val(b["mlp"]["wg"]),
                     wo_mlp=val(b["mlp"]["wo"]))
        if "ssm" in b:
            layer.update({k: val(b["ssm"][k]) for k in
                          ("w_in", "conv", "wB", "wC", "w_dt", "w_down")})
        out.append(layer)
    return out


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_draws_the_programs_weights(name):
    from repro.models.model import Model
    config, ref = _small(name)
    cfg = harness.model_config(config)
    key = jax.random.PRNGKey(harness.engine_seed(3))
    prog = Model(cfg).init(key)
    mine = ref.init(key, config["model"])
    np.testing.assert_array_equal(np.asarray(mine["embed"], np.float32),
                                  np.asarray(prog["embed"].value, np.float32))
    if "unembed" in mine:
        np.testing.assert_array_equal(
            np.asarray(mine["unembed"], np.float32),
            np.asarray(prog["unembed"].value, np.float32))
    for i, layer in enumerate(_program_weights(prog, cfg)):
        for k, v in layer.items():
            np.testing.assert_array_equal(
                np.asarray(mine["layers"][k][i], np.float32), v, err_msg=k)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_logits_equal_the_programs_in_float32(name):
    from repro.models.model import Model
    config, ref = _small(name, dtype="float32")
    cfg = harness.model_config(config)
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 1, 512)
    with jax.default_matmul_precision("highest"):
        prog, _ = Model(cfg).forward(Model(cfg).init(key),
                                     {"tokens": tokens[None]})
    mine = ref.forward(ref.init(key, config["model"]), tokens,
                       config["model"], refcore.Matmul("exact"))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(prog[0]),
                               atol=2e-4, rtol=2e-4)


def test_token_gaps():
    logits = jnp.asarray([[0.0, 2.0, 1.0], [3.0, 0.5, 3.0]])
    assert np.allclose(refcore.token_gaps(logits, jnp.asarray([2, 0])),
                       [1.0, 0.0])


def test_fp8_matmul_is_coarser_than_exact():
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    exact = refcore.Matmul("exact")(a, w)
    low = refcore.Matmul("fp8")(a, w)
    err = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < err < 0.2


def test_flops_count_each_token_once():
    from chipbench import flops
    c = json.loads((CONFIGS / "olmo1b.json").read_text())["model"]
    d, f = c["d_model"], c["d_ff"]
    assert flops.layer_params(c) == 4 * d * d + 3 * d * f
    per_key = 4 * c["n_heads"] * c["head_dim"] * c["n_layers"]
    assert flops.decode_flops(c, 100) == (
        2 * 16 * flops.layer_params(c) + 100 * per_key + 2 * d * 50304)
    # a prompt attends causally and computes the head at one position
    assert flops.prefill_flops(c, 3) == (
        3 * 2 * 16 * flops.layer_params(c) + (1 + 2 + 3) * per_key
        + 2 * d * 50304)
    # sliding layers attend to at most the window, global ones to all
    h = dict(c, n_layers=32, n_heads=25, n_kv_heads=5, head_dim=64,
             sliding_window=1024, global_layers=[0, 15, 31])
    assert flops.attention_flops(h, 5000) == 4 * 25 * 64 * (
        3 * 5000 + 29 * 1024)
    assert flops.attention_bytes(h, 5000) == 2 * 5 * 64 * 2 * (
        3 * 5000 + 29 * 1024)
    # state-space heads beside attention add their projections and scan
    s = dict(h, hybrid=True, ssm_state=16, ssm_expand=2, conv_width=4)
    e = 2 * h["d_model"]
    assert flops.layer_params(s) - flops.layer_params(h) == (
        h["d_model"] * 2 * e + 4 * e + 2 * e * 25 * 16 + e * 25
        + e * h["d_model"])
