"""Transformer assembly: decoder-only LMs, MoE/MLA variants, xLSTM stacks,
hymba hybrid blocks and the seamless encoder-decoder — one init/apply family
driven entirely by ModelConfig.

Layer stacking: homogeneous stacks (dense/moe/mla/encdec) are *scanned* —
per-layer params stacked on a leading "layers" axis and iterated with
lax.scan, keeping HLO size depth-independent (critical for the 96-layer
dry-run cells on a single-core compile host).  Heterogeneous stacks
(xLSTM's mLSTM/sLSTM alternation, hymba's global/sliding split) unroll in
Python.  Remat policy wraps the scanned body.

Cache layout (decode): one pytree per layer, stacked on the scan axis for
scanned stacks; see init_cache.  Attention caches carry an explicit "pos"
slot array so sliding-window layers can use ring buffers (bounded memory at
long_500k) with exact masking.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import ssm
from .layers import (CacheRows, Params, apply_norm, attention_block,
                     init_attention, init_mla, init_moe, init_mlp, init_norm,
                     make_param, mla_block, mlp_block, moe_block, pvalue,
                     shard_hint)

F32 = jnp.float32


# ---------------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------------

def _block_kind(cfg, layer_idx: int) -> str:
    if cfg.family == "ssm":
        if cfg.slstm_every and (layer_idx % cfg.slstm_every == cfg.slstm_every - 1):
            return "slstm"
        return "mlstm"
    if cfg.hybrid:
        return "hybrid"
    if cfg.n_routed_experts and not (layer_idx == 0 and cfg.first_layer_dense):
        return "moe"
    return "dense"


def init_block(key, cfg, layer_idx: int, *, cross_attention: bool = False) -> Params:
    kind = _block_kind(cfg, layer_idx)
    ks = jax.random.split(key, 10)
    p: Params = {}
    if kind == "mlstm":
        p["norm"] = init_norm(ks[0], cfg.d_model, cfg.norm_kind, cfg.dtype)
        p["mix"] = ssm.init_mlstm(ks[1], cfg)
        return p
    if kind == "slstm":
        p["norm"] = init_norm(ks[0], cfg.d_model, cfg.norm_kind, cfg.dtype)
        p["mix"] = ssm.init_slstm(ks[1], cfg)
        return p

    p["attn_norm"] = init_norm(ks[0], cfg.d_model, cfg.norm_kind, cfg.dtype)
    if cfg.use_mla:
        p["attn"] = init_mla(ks[1], cfg)
    else:
        p["attn"] = init_attention(ks[1], cfg)
    if kind == "hybrid":
        p["ssm"] = ssm.init_mamba(ks[2], cfg)
        p["attn_out_norm"] = init_norm(ks[3], cfg.d_model, "rmsnorm", cfg.dtype)
        p["ssm_out_norm"] = init_norm(ks[4], cfg.d_model, "rmsnorm", cfg.dtype)
    if cross_attention:
        p["cross_norm"] = init_norm(ks[5], cfg.d_model, cfg.norm_kind, cfg.dtype)
        p["cross"] = init_attention(ks[6], cfg)
    p["mlp_norm"] = init_norm(ks[7], cfg.d_model, cfg.norm_kind, cfg.dtype)
    if kind == "moe":
        p["mlp"] = init_moe(ks[8], cfg)
    else:
        width = cfg.d_ff if cfg.d_ff else cfg.d_expert * (cfg.moe_top_k + cfg.n_shared_experts)
        p["mlp"] = init_mlp(ks[8], cfg, d_ff=width)
    return p


# ---------------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------------

def _layer_window(cfg, layer_idx: int) -> Optional[int]:
    if cfg.sliding_window and layer_idx not in cfg.global_layers:
        return cfg.sliding_window
    return None


def block_apply(p: Params, x: jax.Array, cfg, layer_idx: int, *, mode: str,
                positions: jax.Array, cache: Optional[dict] = None,
                cache_index=None, enc_out: Optional[jax.Array] = None,
                causal: bool = True, rows: CacheRows = CacheRows()):
    """Apply one block.  Returns (x, new_cache, aux_loss, routed): ``routed``
    counts an MoE block's assignments to each expert held here (``None`` in
    a block without experts).

    mode: "train" (no cache), "prefill" (build cache), "decode" (update).
    In decode, ``rows`` says where the decoded rows sit in ``cache``
    (``CacheRows``): by default the cache holds just them, and with slots it
    is the resident cache, whose leaves are updated in place by kind — one
    new entry a row in K/V/pos and latent leaves, the rows' recurrent state
    rewritten, cross-attention K/V only read.
    """
    kind = _block_kind(cfg, layer_idx)
    aux = jnp.zeros((), F32)
    routed = None
    new_cache: dict = {}

    if kind in ("mlstm", "slstm"):
        h = apply_norm(p["norm"], x, cfg.norm_kind)
        state = cache.get("ssm") if cache else None
        if mode == "decode":
            resident, state = state, rows.read_state(state)
        if kind == "mlstm":
            if mode == "decode":
                y, state = ssm.mlstm_step(p["mix"], h, cfg, state)
            else:
                y, state = ssm.mlstm_chunked(p["mix"], h, cfg, state=state)
        else:
            y, state = ssm.slstm_forward(p["mix"], h, cfg, state)
        if mode == "decode":
            state = rows.write_state(resident, state)
        x = x + y
        if mode != "train":
            new_cache["ssm"] = state
        return x, new_cache, aux, routed

    window = _layer_window(cfg, layer_idx)
    h = apply_norm(p["attn_norm"], x, cfg.norm_kind)
    attn_fn = mla_block if cfg.use_mla else attention_block

    if mode == "train":
        if cfg.use_mla:
            y, _ = mla_block(p["attn"], h, cfg, positions=positions)
        else:
            y, _ = attention_block(p["attn"], h, cfg, positions=positions,
                                   window=window, causal=causal)
    elif mode == "prefill":
        if cfg.use_mla:
            y, latents = mla_block(p["attn"], h, cfg, positions=positions,
                                   return_kv=True)
            new_cache["kv"] = _assemble_mla_cache(latents, cache["kv"], positions)
        else:
            y, kv = attention_block(p["attn"], h, cfg, positions=positions,
                                    window=window, causal=causal, return_kv=True)
            new_cache["kv"] = _assemble_kv_cache(kv, cache["kv"], positions)
    else:  # decode
        if cfg.use_mla:
            y, kvc = mla_block(p["attn"], h, cfg, positions=positions,
                               cache=cache["kv"], cache_index=cache_index,
                               rows=rows)
        else:
            y, kvc = _ring_decode_attention(p["attn"], h, cfg, positions=positions,
                                            cache=cache["kv"], cache_index=cache_index,
                                            window=window, rows=rows)
        new_cache["kv"] = kvc

    if kind == "hybrid":
        sstate = cache.get("ssm") if cache else None
        if mode == "decode":
            ys, new_state = ssm.mamba_step(p["ssm"], h, cfg,
                                           rows.read_state(sstate))
            sstate = rows.write_state(sstate, new_state)
        else:
            ys, sstate = ssm.mamba_chunked(p["ssm"], h, cfg, state=sstate)
        y = 0.5 * (apply_norm(p["attn_out_norm"], y, "rmsnorm")
                   + apply_norm(p["ssm_out_norm"], ys, "rmsnorm"))
        if mode != "train":
            new_cache["ssm"] = sstate
    x = x + y

    if "cross" in p:
        hc = apply_norm(p["cross_norm"], x, cfg.norm_kind)
        if mode == "decode":
            kv = (rows.read(cache["cross_k"]), rows.read(cache["cross_v"]))
        else:
            ek = jnp.einsum("bsd,dhk->bshk", enc_out, pvalue(p["cross"]["wk"]))
            ev = jnp.einsum("bsd,dhk->bshk", enc_out, pvalue(p["cross"]["wv"]))
            kv = (ek, ev)
            if mode == "prefill":
                new_cache["cross_k"], new_cache["cross_v"] = ek, ev
        if mode == "decode":
            new_cache["cross_k"] = cache["cross_k"]
            new_cache["cross_v"] = cache["cross_v"]
        yc, _ = attention_block(p["cross"], hc, cfg, positions=positions,
                                kv_override=kv, causal=False)
        x = x + yc

    h = apply_norm(p["mlp_norm"], x, cfg.norm_kind)
    if kind == "moe":
        # serving routes every token (prefill and decode); training keeps
        # its capacity factor
        y, aux, routed = moe_block(p["mlp"], h, cfg,
                                   capacity_factor=cfg.capacity_factor,
                                   no_drop=(mode != "train"))
    else:
        y = mlp_block(p["mlp"], h, cfg)
    x = x + y
    x = shard_hint(x, ("batch", "seq", "embed"))
    return x, new_cache, aux, routed


# ---------------------------------------------------------------------------------
# KV cache plumbing
# ---------------------------------------------------------------------------------

def _assemble_kv_cache(kv: tuple, template: dict, positions) -> dict:
    """One-shot prefill cache write: pad computed K/V to the cache length.

    Ring (sliding) caches keep only the last C positions.
    """
    k, v = kv
    ck = template["k"]
    cap = ck.shape[1]
    s = k.shape[1]
    pos = positions[0] if positions.ndim > 1 else positions  # (S,)
    if s <= cap:
        pad = [(0, 0), (0, cap - s), (0, 0), (0, 0)]
        newk = jnp.pad(k.astype(ck.dtype), pad)
        newv = jnp.pad(v.astype(ck.dtype), pad)
        newpos = jnp.concatenate([pos, jnp.full((cap - s,), -1, pos.dtype)])
    else:  # keep the ring tail
        newk, newv = k[:, -cap:].astype(ck.dtype), v[:, -cap:].astype(ck.dtype)
        newpos = pos[-cap:]
    return {"k": newk, "v": newv, "pos": jnp.broadcast_to(newpos, (k.shape[0], cap))}


def _assemble_mla_cache(latents: tuple, template: dict, positions) -> dict:
    c, k_pe = latents
    cap = template["c"].shape[1]
    s = c.shape[1]
    pos = positions[0] if positions.ndim > 1 else positions
    pad2 = [(0, 0), (0, cap - s), (0, 0)]
    return {"c": jnp.pad(c.astype(template["c"].dtype), pad2),
            "k_pe": jnp.pad(k_pe.astype(template["k_pe"].dtype), pad2),
            "pos": jnp.broadcast_to(
                jnp.concatenate([pos, jnp.full((cap - s,), -1, pos.dtype)]),
                (c.shape[0], cap))}


def _ring_decode_attention(p, h, cfg, *, positions, cache, cache_index, window,
                           rows: CacheRows):
    """Decode attention against a (possibly ring) cache with explicit pos:
    each row's new K/V/pos entry is written at ``index % cap`` where ``rows``
    places it, then the rows are read back and attended over."""
    import math as _math
    from .layers import _repeat_kv, apply_rope, rope_table
    b, s, d = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, pvalue(p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", h, pvalue(p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", h, pvalue(p["wv"]))
    if cfg.qkv_bias:
        q, k, v = q + pvalue(p["bq"]), k + pvalue(p["bk"]), v + pvalue(p["bv"])
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    if cfg.rope:
        sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    cap = cache["pos"].shape[-1]
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    slot = idx % cap
    new_cache = {"k": rows.write_at(cache["k"], slot, k[:, 0]),
                 "v": rows.write_at(cache["v"], slot, v[:, 0]),
                 "pos": rows.write_at(cache["pos"], slot, idx)}
    ck, cv, cpos = (rows.read(new_cache[n]) for n in ("k", "v", "pos"))
    qpos = idx[:, None]

    with jax.named_scope("KERNEL_paged_attention"):
        kk = _repeat_kv(ck, cfg.n_heads // cfg.n_kv_heads)
        vv = _repeat_kv(cv, cfg.n_heads // cfg.n_kv_heads)
        scale = 1.0 / _math.sqrt(cfg.head_dim)
        # bf16 operands, f32 MXU accumulation on TPU: never materialize an
        # f32 copy of the KV cache (§Perf iteration C2 — the Pallas paged
        # kernel does this natively in VMEM)
        from .layers import einsum_f32acc
        logits = einsum_f32acc("bqhd,bkhd->bhqk", q, kk) * scale
        qp = qpos.reshape(b, 1, 1, 1).astype(jnp.int32)
        kp = cpos[:, None, None, :].astype(jnp.int32)
        mask = (kp >= 0) & (kp <= qp)
        if window is not None:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = einsum_f32acc("bhqk,bkhd->bqhd", probs, vv).astype(h.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, pvalue(p["wo"]))
    return y, new_cache


# ---------------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------------

def init_layer_cache(cfg, layer_idx: int, batch: int, max_len: int,
                     enc_len: int = 0, dtype=jnp.bfloat16) -> dict:
    kind = _block_kind(cfg, layer_idx)
    c: dict = {}
    if kind == "mlstm":
        return {"ssm": ssm.init_mlstm_state(batch, cfg)}
    if kind == "slstm":
        return {"ssm": ssm.init_slstm_state(batch, cfg)}
    window = _layer_window(cfg, layer_idx)
    cap = min(max_len, window) if window else max_len
    if cfg.use_mla:
        c["kv"] = {"c": jnp.zeros((batch, cap, cfg.kv_lora_rank), dtype),
                   "k_pe": jnp.zeros((batch, cap, cfg.rope_head_dim), dtype),
                   "pos": jnp.full((batch, cap), -1, jnp.int32)}
    else:
        c["kv"] = {"k": jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim), dtype),
                   "v": jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim), dtype),
                   "pos": jnp.full((batch, cap), -1, jnp.int32)}
    if kind == "hybrid":
        c["ssm"] = ssm.init_mamba_state(batch, cfg)
    if cfg.encoder_layers:
        c["cross_k"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype)
        c["cross_v"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype)
    return c


# ---------------------------------------------------------------------------------
# Stacked (scanned) layers
# ---------------------------------------------------------------------------------

from .layers import Param, is_param as _is_param


def stack_params(per_layer: list[Params]) -> Params:
    """Stack a list of identically-structured param trees on a new leading
    "layers" axis."""
    def stack(*leaves):
        return Param(jnp.stack([l.value for l in leaves]),
                     ("layers",) + leaves[0].axes)
    return jax.tree.map(stack, *per_layer, is_leaf=_is_param)


def _slice_layer(stacked: Params, values) -> Params:
    """Rebuild a per-layer param tree from scanned leaf values."""
    flat_axes = [l.axes[1:] for l in jax.tree.leaves(stacked, is_leaf=_is_param)]
    flat_vals = jax.tree.leaves(values)
    rebuilt = [Param(v, a) for v, a in zip(flat_vals, flat_axes)]
    treedef = jax.tree.structure(stacked, is_leaf=_is_param)
    return jax.tree.unflatten(treedef, rebuilt)


def _remat(fn, cfg):
    if not cfg.remat or cfg.remat_policy == "full":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def scan_blocks(stacked: Params, x: jax.Array, cfg, *, mode: str, positions,
                cache: Optional[dict], cache_index, enc_out, layer0_offset: int,
                slots=None):
    """lax.scan over a homogeneous layer stack.

    cache (if given) is a per-layer pytree stacked on axis 0 (same order).
    With ``slots`` (decode on the resident cache) the stacked cache rides in
    the carry and each layer updates it in place at (layer, slot, ...), so
    no layer's cache is sliced out and none is stacked again as the scan's
    output.
    Returns (x, new_stacked_cache, total_aux, routed): ``routed`` stacks each
    layer's ``block_apply`` counts (``None`` without experts).
    """
    values = jax.tree.map(lambda p: p.value, stacked, is_leaf=_is_param)

    if slots is not None:
        def resident_body(carry, xs):
            h, aux, cache = carry
            layer_values, layer = xs
            h, cache, a, routed = block_apply(
                _slice_layer(stacked, layer_values), h, cfg, layer0_offset,
                mode=mode, positions=positions, cache=cache,
                cache_index=cache_index, rows=CacheRows(slots, (layer,)))
            return (h, aux + a, cache), routed

        n_layers = jax.tree.leaves(values)[0].shape[0]
        (x, aux, cache), routed = lax.scan(
            resident_body, (x, jnp.zeros((), F32), cache),
            (values, jnp.arange(n_layers)))
        return x, cache, aux, routed

    def body(carry, xs):
        h, aux = carry
        layer_values, layer_cache = xs
        p = _slice_layer(stacked, layer_values)
        h, new_cache, a, routed = block_apply(
            p, h, cfg, layer0_offset, mode=mode, positions=positions,
            cache=layer_cache, cache_index=cache_index, enc_out=enc_out)
        return (h, aux + a), (new_cache, routed)

    body = _remat(body, cfg) if mode == "train" else body
    (x, aux), (new_cache, routed) = lax.scan(
        body, (x, jnp.zeros((), F32)), (values, cache))
    return x, new_cache, aux, routed
