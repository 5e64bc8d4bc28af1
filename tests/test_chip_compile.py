"""Compile the serving path for a TPU v5e that is described, not attached.

What the chip's compiler refuses (a block not aligned to the tiling, too
much VMEM, a program larger than HBM) fails here at no chip time.  Nothing
runs: results and times need the chip.  The topology is described inside a
fixture so that importing this file never loads the TPU library.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.dequant.ops import dequant
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.model import Model
from repro.serving.engine import (packed_step, packed_step_of,
                                  prefill_logits_cache)

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture
def tpu_branch(monkeypatch):
    """The program picks its TPU code (kernels, bf16 MXU operands) from
    ``jax.default_backend()``, which sees this host's CPU: steer it here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.fixture(scope="module")
def olmo():
    return Model(get_config("olmo-1b"))


def _params_of(model, sharding):
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    return jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype), shapes)


def test_flash_attention_compiles_for_olmo_prefill(one_chip, tpu_branch):
    q = _on(one_chip, (1, 512, 16, 128), jnp.bfloat16)
    compiled = _compile(flash_attention, q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_at_batch_8(one_chip, tpu_branch):
    batch, heads, d, page, pages_each = 8, 16, 128, 16, 1024 // 16
    q = _on(one_chip, (batch, heads, d), jnp.bfloat16)
    pages = _on(one_chip, (batch * pages_each, page, heads, d), jnp.bfloat16)
    tables = _on(one_chip, (batch, pages_each), jnp.int32)
    lengths = _on(one_chip, (batch,), jnp.int32)
    compiled = _compile(paged_attention, q, pages, pages, tables, lengths)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_dequant_compiles(one_chip, tpu_branch, codec):
    codes = _on(one_chip, (4096, 128), jnp.uint8)
    scales = _on(one_chip, (4096,), jnp.float32)
    compiled = _compile(functools.partial(dequant, codec=codec), codes, scales)
    assert "tpu_custom_call" in compiled.as_text()


def _olmo_cache(model, sharding):
    return jax.tree.map(
        lambda s: _on(sharding, s.shape, s.dtype),
        jax.eval_shape(lambda: model.init_cache(8, 1024)))


def test_olmo_packed_decode_step_fits_one_chip(one_chip, tpu_branch, olmo):
    """The engine's whole packed step (the 16-layer decode on the resident
    cache) at max_batch=8, max_len=1024, with params and cache as the
    engine holds them."""
    params = _params_of(olmo, one_chip)
    caches = _olmo_cache(olmo, one_chip)
    rows = _on(one_chip, (8,), jnp.int32)
    tokens = _on(one_chip, (8, 1), jnp.int32)
    compiled = _compile(functools.partial(packed_step, olmo),
                        params, caches, tokens, rows, rows)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"


#: ops that hand a whole buffer on without writing one
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def test_olmo_packed_step_decodes_in_place(one_chip, tpu_branch, olmo):
    """The engine's jitted packed step, cache donated, at width 8: the
    resident cache is aliased to the output, the step needs next to no
    scratch, and no op outside the layer loop copies or rebuilds a whole
    K or V cache (the loop writes one row a layer and slot in place)."""
    params = _params_of(olmo, one_chip)
    caches = _olmo_cache(olmo, one_chip)
    inputs = _on(one_chip, (3, 8), jnp.int32)
    compiled = packed_step_of(olmo).program.lower(params, caches,
                                                  inputs).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(caches))
    assert cache_bytes > 1e9
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes, mem.alias_size_in_bytes
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    whole = [m.group(1) for m in re.finditer(
        r"= bf16\[16,8,1024,16,128\]\{[^}]*\} ([\w-]+)\(", entry)]
    assert whole, "no whole-cache buffer found in ENTRY"
    assert set(whole) <= _PASS_THROUGH, sorted(set(whole) - _PASS_THROUGH)


def test_olmo_prefill_compiles_at_512_tokens(one_chip, tpu_branch, olmo):
    params = _params_of(olmo, one_chip)
    tokens = _on(one_chip, (1, 512), jnp.int32)
    compiled = prefill_logits_cache.lower(olmo.cfg, params, tokens,
                                          1024).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"


def test_dsv2lite_packed_step_fits_and_decodes_in_place(one_chip, tpu_branch):
    """DeepSeek-V2-Lite as the benchmark serves it (27 layers, 8 of 64
    experts held, latent cache 2048 long) at width 32: weights and resident
    cache fit the chip, the donated cache is aliased to the output, and the
    step needs next to no scratch."""
    import dataclasses
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              n_experts_held=8)
    model = Model(cfg)
    params = _params_of(model, one_chip)
    caches = jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: model.init_cache(32, 2048)))
    inputs = _on(one_chip, (3, 32), jnp.int32)
    compiled = packed_step_of(model).program.lower(params, caches,
                                                   inputs).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes > 2e9
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"
