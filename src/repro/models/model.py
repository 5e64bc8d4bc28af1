"""Top-level model API: init / forward / loss / prefill / decode_step.

Everything is a pure function of (params, cfg, inputs) so the launchers can
jit/pjit them directly; `Model` is a thin binder used by examples and the
serving engine.

Batch contracts:
  train:   {"tokens": (B,S) i32, "targets": (B,S) i32, "loss_mask": (B,S) f32}
           + "patch_embeds" (B,P,D) for vlm / "frames" (B,F,D) for audio encdec
  prefill: tokens (B,S) (+ frontend embeds); returns (last_logits, cache)
  decode:  tokens (B,1) + cache + index; returns (logits, cache)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .layers import (CacheRows, make_param, apply_norm, init_norm, pvalue,
                     shard_hint)
from .transformer import (block_apply, init_block, init_layer_cache,
                          scan_blocks, stack_params, _block_kind)

F32 = jnp.float32


# ---------------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------------

def init_params(key, cfg) -> dict:
    ks = jax.random.split(key, 8)
    params: dict = {
        "embed": make_param(ks[0], (cfg.vocab_size, cfg.d_model),
                            ("vocab", "embed"), fan_in=cfg.d_model, dtype=cfg.dtype),
        "final_norm": init_norm(ks[1], cfg.d_model, cfg.norm_kind, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = make_param(ks[2], (cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"), fan_in=cfg.d_model,
                                       dtype=cfg.dtype)

    start = 1 if (cfg.n_routed_experts and cfg.first_layer_dense) else 0
    layer_keys = jax.random.split(ks[3], cfg.n_layers)
    cross = cfg.encoder_layers > 0
    if start:
        params["block0"] = init_block(layer_keys[0], cfg, 0, cross_attention=cross)
    per_layer = [init_block(layer_keys[i], cfg, i, cross_attention=cross)
                 for i in range(start, cfg.n_layers)]
    if cfg.scan_layers:
        params["blocks"] = stack_params(per_layer)
    else:
        params["blocks"] = per_layer

    if cfg.encoder_layers:
        enc_cfg = dataclasses.replace(
            cfg, n_routed_experts=0, hybrid=False, ssm_kind="", use_mla=False)
        enc_keys = jax.random.split(ks[4], cfg.encoder_layers)
        enc_layers = [init_block(k, enc_cfg, i) for i, k in enumerate(enc_keys)]
        params["encoder"] = {
            "blocks": stack_params(enc_layers) if cfg.scan_layers else enc_layers,
            "final_norm": init_norm(ks[5], cfg.d_model, cfg.norm_kind, cfg.dtype),
        }
    return params


# ---------------------------------------------------------------------------------
# Shared trunk
# ---------------------------------------------------------------------------------

def _embed_tokens(params, cfg, tokens):
    x = jnp.take(pvalue(params["embed"]), tokens, axis=0)
    return shard_hint(x, ("batch", "seq", "embed"))


def _encoder_forward(params, cfg, frames):
    """Bidirectional encoder over precomputed frame embeddings (stub input)."""
    enc_cfg = dataclasses.replace(
        cfg, n_routed_experts=0, hybrid=False, ssm_kind="", use_mla=False)
    x = frames
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    enc = params["encoder"]
    if cfg.scan_layers:
        x, _, _, _ = scan_blocks(enc["blocks"], x, enc_cfg, mode="train",
                                 positions=positions, cache=None,
                                 cache_index=None, enc_out=None,
                                 layer0_offset=0)
    else:
        for i, p in enumerate(enc["blocks"]):
            x, _, _, _ = block_apply(p, x, enc_cfg, i, mode="train",
                                     positions=positions, causal=False)
    return apply_norm(enc["final_norm"], x, cfg.norm_kind)


def _trunk(params, cfg, x, *, mode, positions, caches=None, cache_index=None,
           enc_out=None, slots=None):
    """Run all decoder blocks.  caches layout mirrors params['blocks'];
    with ``slots`` it is the resident cache of every slot (decode_step).
    Returns (x, new_caches, aux, routed): ``routed`` (MoE layers, experts
    held) int32 assignment counts, ``None`` in a model without experts."""
    aux = jnp.zeros((), F32)
    rows = CacheRows(slots)
    start = 1 if "block0" in params else 0
    new_caches: dict = {}
    routed = []                 # (layers, held) pieces, in layer order
    if start:
        c0 = caches["block0"] if caches else None
        x, nc0, a0, r0 = block_apply(params["block0"], x, cfg, 0, mode=mode,
                                     positions=positions, cache=c0,
                                     cache_index=cache_index, enc_out=enc_out,
                                     rows=rows)
        aux += a0
        if r0 is not None:
            routed.append(r0[None])
        if mode != "train":
            new_caches["block0"] = nc0
    blk_cache = caches["blocks"] if caches else None
    if cfg.scan_layers:
        x, ncs, a, r = scan_blocks(params["blocks"], x, cfg, mode=mode,
                                   positions=positions, cache=blk_cache,
                                   cache_index=cache_index, enc_out=enc_out,
                                   layer0_offset=start, slots=slots)
        aux += a
        if r is not None:
            routed.append(r)
        if mode != "train":
            new_caches["blocks"] = ncs
    else:
        ncs = []
        for i, p in enumerate(params["blocks"]):
            c = None if blk_cache is None else _index_cache(blk_cache, i)
            x, nc, a, r = block_apply(p, x, cfg, start + i, mode=mode,
                                      positions=positions, cache=c,
                                      cache_index=cache_index, enc_out=enc_out,
                                      rows=rows)
            aux += a
            if r is not None:
                routed.append(r[None])
            ncs.append(nc)
        if mode != "train":
            new_caches["blocks"] = ncs
    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    return x, new_caches, aux, (jnp.concatenate(routed) if routed else None)


def _index_cache(blk_cache, i):
    return blk_cache[i] if isinstance(blk_cache, list) else jax.tree.map(
        lambda t: t[i], blk_cache)


def _logits(params, cfg, x):
    if cfg.tie_embeddings:
        w = pvalue(params["embed"]).T
    else:
        w = pvalue(params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", x, w).astype(cfg.logits_dtype)
    return shard_hint(logits, ("batch", "seq", "vocab"))


def _assemble_inputs(params, cfg, batch) -> tuple[jax.Array, jax.Array, Any, int]:
    """Token/frontend fusion.  Returns (x, positions, enc_out, prefix_len)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    enc_out = None
    prefix = 0
    if cfg.family == "vlm" and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix = patches.shape[1]
    if cfg.encoder_layers:
        enc_out = _encoder_forward(params, cfg, batch["frames"].astype(x.dtype))
    total = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(total)[None], (b, total))
    return x, positions, enc_out, prefix


# ---------------------------------------------------------------------------------
# Train forward / loss
# ---------------------------------------------------------------------------------

def forward(params, cfg, batch) -> tuple[jax.Array, jax.Array]:
    """Training forward.  Returns (logits over token positions, aux_loss)."""
    x, positions, enc_out, prefix = _assemble_inputs(params, cfg, batch)
    x, _, aux, _ = _trunk(params, cfg, x, mode="train", positions=positions,
                          enc_out=enc_out)
    if prefix:
        x = x[:, prefix:]
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg, batch) -> tuple[jax.Array, dict]:
    logits, aux = forward(params, cfg, batch)
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(targets, F32)
    logits = logits.astype(F32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - tgt_logit) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    zloss = cfg.z_loss * ((logz * mask) ** 2).sum() / denom
    total = loss + zloss + cfg.moe_aux_weight * aux
    return total, {"loss": loss, "z_loss": zloss, "moe_aux": aux,
                   "tokens": denom}


# ---------------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, enc_len: int = 0,
               dtype=jnp.bfloat16) -> dict:
    start = 1 if (cfg.n_routed_experts and cfg.first_layer_dense) else 0
    caches: dict = {}
    if start:
        caches["block0"] = init_layer_cache(cfg, 0, batch, max_len, enc_len, dtype)
    per_layer = [init_layer_cache(cfg, i, batch, max_len, enc_len, dtype)
                 for i in range(start, cfg.n_layers)]
    if cfg.scan_layers:
        caches["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    else:
        caches["blocks"] = per_layer
    return caches


def prefill(params, cfg, batch, max_len: int, *, routed: bool = False):
    """Process the full prompt, build the cache in one shot.

    Returns (last_position_logits, caches, next_index), and with ``routed``
    the (MoE layers, experts held) assignment counts as well (``None``
    without experts).
    """
    x, positions, enc_out, prefix = _assemble_inputs(params, cfg, batch)
    b, total = positions.shape
    enc_len = cfg.frontend_tokens if cfg.encoder_layers else 0
    caches = init_cache(cfg, b, max_len, enc_len,
                        dtype=cfg.dtype)
    x, new_caches, _, counts = _trunk(params, cfg, x, mode="prefill",
                                      positions=positions, caches=caches,
                                      cache_index=0, enc_out=enc_out)
    logits = _logits(params, cfg, x[:, -1:])
    if routed:
        return logits, new_caches, total, counts
    return logits, new_caches, total


def decode_step(params, cfg, caches, tokens, index, slots=None, *,
                routed: bool = False):
    """One decode step.  tokens: (B,1); index: scalar or (B,) per-sequence
    current lengths (continuous batching).  Returns (logits, caches), and
    with ``routed`` the (MoE layers, experts held) assignment counts as well
    (``None`` without experts).

    Without ``slots`` the cache holds the B decoded rows, and the step
    returns their new cache.  With ``slots`` (B,) it is the resident cache
    of every slot: row i decodes slot ``slots[i]``, its new K/V/pos entries
    are written where that slot resides (at ``index % cap``) before it is
    attended over, and the cache returned is the same tree updated in place
    — donate it.  Rows that share a slot must carry the same token and
    index."""
    b = tokens.shape[0]
    x = _embed_tokens(params, cfg, tokens)
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    positions = index[:, None]
    x, new_caches, _, counts = _trunk(params, cfg, x, mode="decode",
                                      positions=positions, caches=caches,
                                      cache_index=index, enc_out=None,
                                      slots=slots)
    if routed:
        return _logits(params, cfg, x), new_caches, counts
    return _logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------------
# Binder
# ---------------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: Any

    def init(self, key):
        return init_params(key, self.cfg)

    def forward(self, params, batch):
        return forward(params, self.cfg, batch)

    def loss(self, params, batch):
        return loss_fn(params, self.cfg, batch)

    def prefill(self, params, batch, max_len: int, *, routed: bool = False):
        return prefill(params, self.cfg, batch, max_len, routed=routed)

    def decode_step(self, params, caches, tokens, index, slots=None, *,
                    routed: bool = False):
        return decode_step(params, self.cfg, caches, tokens, index, slots,
                           routed=routed)

    def init_cache(self, batch: int, max_len: int, enc_len: int = 0):
        return init_cache(self.cfg, batch, max_len, enc_len, dtype=self.cfg.dtype)
