"""Model building blocks: norms, rotary embeddings, attention variants
(GQA / qk-norm / bias / MLA), MLPs (SwiGLU / squared-ReLU / GELU) and MoE.

Pure-functional JAX: params are pytrees of jnp arrays; every block is a
function (params, x, ...) -> y.  Sharding is expressed through *logical axis*
names attached to each parameter (see shardings.py); activations get
`with_sharding_constraint` hints at layer boundaries.

Attention exposes three implementations selected by config:
  dense      — plain einsum softmax attention (smoke tests, short seqs)
  blockwise  — flash-attention algorithm in pure jnp (lax.scan over KV
               blocks, running max/sum): O(block) memory, compiles on any
               backend — the dry-run default for long sequences
  pallas     — the TPU Pallas kernels in repro.kernels (prefill flash /
               paged decode), numerically validated against ref oracles
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = dict  # pytree of Param leaves
Axes = tuple  # logical axis names, one per dim


@jax.tree_util.register_pytree_node_class
class Param:
    """A parameter leaf: array value + static logical-axis names.

    Registered pytree node with `axes` as aux data, so param trees survive
    jax.eval_shape / jit / grad with sharding metadata intact (the dry-run
    builds ShapeDtypeStruct trees from eval_shape output).
    """

    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Axes):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    def __getitem__(self, key):  # back-compat dict-style access
        return getattr(self, key)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"Param(shape={shape}, axes={self.axes})"


def is_param(x) -> bool:
    return isinstance(x, Param)


# ---------------------------------------------------------------------------------
# Initialization helpers
# ---------------------------------------------------------------------------------

def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def make_param(key, shape: tuple, axes: Axes, *, fan_in: Optional[int] = None,
               dtype=jnp.bfloat16, zeros: bool = False, ones: bool = False):
    if zeros:
        v = jnp.zeros(shape, dtype)
    elif ones:
        v = jnp.ones(shape, dtype)
    else:
        v = _dense_init(key, shape, fan_in if fan_in is not None else shape[0], dtype)
    return Param(v, axes)


def pvalue(p) -> jax.Array:
    return p.value


# ---------------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------------

def init_norm(key, d: int, kind: str, dtype=jnp.bfloat16) -> Params:
    if kind == "nonparametric_ln":
        return {}
    if kind == "rmsnorm":
        return {"scale": make_param(key, (d,), ("embed",), ones=True, dtype=dtype)}
    if kind == "layernorm":
        k1, k2 = jax.random.split(key)
        return {"scale": make_param(k1, (d,), ("embed",), ones=True, dtype=dtype),
                "bias": make_param(k2, (d,), ("embed",), zeros=True, dtype=dtype)}
    raise ValueError(f"unknown norm kind {kind}")


def apply_norm(params: Params, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * pvalue(params["scale"]).astype(jnp.float32)).astype(x.dtype)
    # layernorm / non-parametric layernorm
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * pvalue(params["scale"]).astype(jnp.float32) + \
            pvalue(params["bias"]).astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------------

def rope_table(positions: jax.Array, dim: int, theta: float = 10000.0,
               inv_freq=None, mscale: float = 1.0):
    """(positions...) -> (sin, cos) of shape positions.shape + (dim/2,);
    ``inv_freq`` (dim/2,) replaces the plain frequencies ``theta^(-2i/dim)``
    and ``mscale`` scales both (YaRN, ``yarn_rope``)."""
    half = dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    return sin, cos


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_rope(cfg, dim: int) -> tuple:
    """(inv_freq, mscale) of ``rope_table`` for ``dim`` rope dims under
    ``cfg``'s YaRN scaling: frequencies whose wavelength fits the original
    context ``L0`` keep theirs (``f_extra = theta^(-2i/dim)``), the longest
    ones are divided by the factor (``f_inter``), and a linear ramp over the
    pairs ``i`` between the two correction dims blends them.  Plain RoPE
    (``(None, 1.0)``) where ``cfg.yarn_factor`` is 0."""
    factor = cfg.yarn_factor
    if not factor:
        return None, 1.0
    base, l0 = cfg.rope_theta, cfg.yarn_original_max_len

    def correction_dim(rotations):
        return dim * math.log(l0 / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    f_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = f_extra / factor * ramp + f_extra * (1 - ramp)
    mscale = (yarn_mscale(factor, cfg.yarn_mscale)
              / yarn_mscale(factor, cfg.yarn_mscale_all_dim))
    return jnp.asarray(inv_freq, jnp.float32), mscale


def mla_softmax_scale(cfg) -> float:
    """MLA's softmax scale: ``1/sqrt(nope + rope dims)``, times YaRN's
    ``mscale(factor, mscale_all_dim)^2`` (one without YaRN)."""
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
            / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim))


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., seq, heads, dim); sin/cos: (..., seq, dim/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s, c = sin[..., None, :], cos[..., None, :]  # broadcast over heads
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------------

def einsum_f32acc(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """Matmul with f32 accumulation, TPU-native operand dtypes.

    TPU: bf16 operands straight into the MXU with preferred_element_type=f32
    — no f32 materialization of large operands (KV caches!) and full bf16
    MXU throughput.  CPU (tests/smoke): explicit f32 compute — the CPU thunk
    cannot execute mixed bf16->f32 dots, and f32 keeps decode bit-aligned
    with the f32 reference attention.
    """
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class CacheRows:
    """Where a decode step's rows sit in one layer's cache leaves.

    By default the leaves hold exactly the decoded rows: row ``i`` is leaf
    row ``i``.  With ``slots`` they are the engine's resident cache of every
    slot, and row ``i`` lives at ``leaf[at + (slots[i],)]``; ``at`` is
    ``(layer,)`` inside a scan-stacked cache and ``()`` in a per-layer one.
    Then a leaf with a position axis (K, V, pos, MLA latents) takes one new
    entry a row in place (``write_at``) and gives back the rows the step
    attends over (``read``); a recurrent state tree gives and takes whole
    rows (``read_state``, ``write_state``).
    """
    slots: Optional[jax.Array] = None
    at: tuple = ()

    def write_at(self, leaf, pos, new):
        """``leaf`` with each row's entry at position ``pos`` set to
        ``new``."""
        rows = ((jnp.arange(new.shape[0]),) if self.slots is None
                else self.at + (self.slots,))
        return leaf.at[rows + (pos,)].set(new.astype(leaf.dtype))

    def read(self, leaf):
        """The decoded rows of ``leaf``, one dynamic slice a row: a TPU
        lowers a gather of them by copying the leaf's whole layer first
        (the whole stacked leaf, where the layer is indexed in the same
        gather)."""
        if self.slots is None:
            return leaf
        lead = len(self.at) + 1
        shape = leaf.shape[lead:]
        return jnp.stack([
            lax.dynamic_slice(leaf, self.at + (self.slots[i],)
                              + (0,) * len(shape),
                              (1,) * lead + shape).reshape(shape)
            for i in range(self.slots.shape[0])])

    def read_state(self, tree):
        """The decoded rows of every leaf of a recurrent state tree (small,
        so gathered)."""
        if self.slots is None:
            return tree
        return jax.tree.map(lambda a: a[self.at + (self.slots,)], tree)

    def write_state(self, tree, new):
        """``tree`` with the decoded rows of every leaf replaced by ``new``'s
        (a step rewrites a row's recurrent state whole)."""
        if self.slots is None:
            return new
        return jax.tree.map(
            lambda a, n: a.at[self.at + (self.slots,)].set(n.astype(a.dtype)),
            tree, new)


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B,S,kv,d) -> (B,S,kv*n_rep,d) for GQA."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(
        b, s, kv * n_rep, d)


def dense_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    window: Optional[int] = None,
                    kv_len: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Plain softmax attention.  q:(B,Sq,H,D) k,v:(B,Sk,H,D); the logits
    are scaled by ``scale`` (default ``1/sqrt(D)``)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    if kv_len is not None:  # decode: mask out unwritten cache slots
        valid = kpos < kv_len
        logits = jnp.where(valid[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, block_kv: int = 1024,
                        q_offset: int = 0, window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Flash-attention algorithm in pure jnp: lax.scan over KV blocks with
    running (max, sum, acc) — O(block) memory, the portable long-seq path.
    The logits are scaled by ``scale`` (default ``1/sqrt(D)``).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nblk = -(-sk // block_kv)
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    qf = q.astype(jnp.float32) * scale
    kb = k.reshape(b, nblk, block_kv, h, d).astype(jnp.float32)
    vb = v.reshape(b, nblk, block_kv, h, d).astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, blk):
        m, s, acc = carry
        kblk, vblk, bidx = blk
        kpos = bidx * block_kv + jnp.arange(block_kv)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk)
        mask = jnp.ones((sq, block_kv), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        mask &= (kpos < sk)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        s_new = s * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vblk)
        return (m_new, s_new, acc_new), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    s0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)
    vb_t = jnp.moveaxis(vb, 1, 0)
    (m, s, acc), _ = lax.scan(step, (m0, s0, acc0),
                              (kb_t, vb_t, jnp.arange(nblk)))
    out = acc / jnp.maximum(s[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B,H,Sq,D)->(B,Sq,H,D)


def attention_core(q, k, v, *, impl: str, causal: bool, q_offset: int = 0,
                   window: Optional[int] = None, kv_len=None,
                   block_kv: int = 1024,
                   scale: Optional[float] = None) -> jax.Array:
    """All attention routes through here under a KERNEL_* named scope, so the
    dry-run HLO analyzer can attribute its HBM traffic and substitute the
    Pallas kernel's (VMEM-resident) byte profile — see launch/hlo_analysis.
    ``scale`` replaces the logits' default ``1/sqrt(D)``."""
    n_rep = q.shape[2] // k.shape[2]
    if impl == "pallas":
        # TPU kernels; GQA handled natively (no KV repeat materialization)
        from repro.kernels.flash_attention import ops as fa_ops
        if kv_len is None and q.shape[1] > 1:
            if scale is not None:   # the kernel scales by 1/sqrt(D) itself
                q = (q * (scale * math.sqrt(q.shape[-1]))).astype(q.dtype)
            return fa_ops.flash_attention(q, k, v, causal=causal, window=window)
        impl = "dense"  # decode path handled by paged kernel at cache level
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if impl == "blockwise":
        if kv_len is not None:
            with jax.named_scope("KERNEL_paged_attention"):
                return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                                       window=window, kv_len=kv_len, scale=scale)
        with jax.named_scope("KERNEL_flash_attention"):
            return blockwise_attention(q, k, v, causal=causal, q_offset=q_offset,
                                       window=window, block_kv=block_kv,
                                       scale=scale)
    scope = "KERNEL_paged_attention" if kv_len is not None else "KERNEL_flash_attention"
    with jax.named_scope(scope):
        return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                               window=window, kv_len=kv_len, scale=scale)


# ---------------------------------------------------------------------------------
# GQA attention block (olmo / qwen / nemotron / internvl / seamless / hymba-attn)
# ---------------------------------------------------------------------------------

def init_attention(key, cfg) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": make_param(ks[0], (d, h, hd), ("embed", "heads", "head_dim"), fan_in=d, dtype=cfg.dtype),
        "wk": make_param(ks[1], (d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in=d, dtype=cfg.dtype),
        "wv": make_param(ks[2], (d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in=d, dtype=cfg.dtype),
        "wo": make_param(ks[3], (h, hd, d), ("heads", "head_dim", "embed"), fan_in=h * hd, dtype=cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = make_param(ks[4], (h, hd), ("heads", "head_dim"), zeros=True, dtype=cfg.dtype)
        p["bk"] = make_param(ks[5], (kv, hd), ("kv_heads", "head_dim"), zeros=True, dtype=cfg.dtype)
        p["bv"] = make_param(ks[6], (kv, hd), ("kv_heads", "head_dim"), zeros=True, dtype=cfg.dtype)
    if cfg.qk_norm:
        k1, k2 = jax.random.split(ks[7])
        p["q_norm"] = init_norm(k1, hd, "rmsnorm", cfg.dtype)
        p["k_norm"] = init_norm(k2, hd, "rmsnorm", cfg.dtype)
    return p


def attention_block(p: Params, x: jax.Array, cfg, *, positions: jax.Array,
                    cache: Optional[dict] = None, cache_index=None,
                    window: Optional[int] = None, causal: bool = True,
                    kv_override: Optional[tuple] = None,
                    return_kv: bool = False):
    """GQA attention.  x: (B, S, D).

    Modes:
      train   — cache=None, return_kv=False: full causal attention.
      prefill — cache=None, return_kv=True: also returns post-rope (k, v) so
                the caller can assemble the KV cache in one shot (no O(S^2)
                dense-masked path; attention runs blockwise).
      decode  — cache={"k","v"} + cache_index: dynamic-slice update + masked
                attention over the cache.
    kv_override: (k, v) for cross-attention (encoder-decoder).
    Returns (y, cache_or_kv_or_None).
    """
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, pvalue(p["wq"]))
    if cfg.qkv_bias:
        q = q + pvalue(p["bq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, pvalue(p["wk"]))
        v = jnp.einsum("bsd,dhk->bshk", x, pvalue(p["wv"]))
        if cfg.qkv_bias:
            k = k + pvalue(p["bk"])
            v = v + pvalue(p["bv"])
    else:
        k, v = kv_override

    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")

    if cfg.rope and kv_override is None:
        sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    if cache is not None and kv_override is None:
        # decode: write new K/V at cache_index, attend over the valid prefix
        ck, cv = cache["k"], cache["v"]
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
        cache = dict(cache, k=ck, v=cv)
        out = attention_core(q, ck, cv, impl="dense", causal=causal,
                             q_offset=cache_index, window=window,
                             kv_len=cache_index + s)
        y = jnp.einsum("bshk,hkd->bsd", out, pvalue(p["wo"]))
        return y, cache

    out = attention_core(q, k, v, impl=cfg.attn_impl, causal=causal,
                         window=window, block_kv=cfg.attn_block_kv)
    y = jnp.einsum("bshk,hkd->bsd", out, pvalue(p["wo"]))
    return y, ((k, v) if return_kv else None)


# ---------------------------------------------------------------------------------
# MLA attention (deepseek-v2): latent KV cache — the residency-maximizing variant
# ---------------------------------------------------------------------------------

def init_mla(key, cfg) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    dc, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dv = cfg.nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": make_param(ks[0], (d, h, dn + dr), ("embed", "heads", "head_dim"), fan_in=d, dtype=cfg.dtype),
        "wdkv": make_param(ks[1], (d, dc + dr), ("embed", "kv_lora"), fan_in=d, dtype=cfg.dtype),
        "kv_norm": init_norm(ks[2], dc, "rmsnorm", cfg.dtype),
        "wuk": make_param(ks[3], (dc, h, dn), ("kv_lora", "heads", "head_dim"), fan_in=dc, dtype=cfg.dtype),
        "wuv": make_param(ks[4], (dc, h, dv), ("kv_lora", "heads", "head_dim"), fan_in=dc, dtype=cfg.dtype),
        "wo": make_param(ks[5], (h, dv, d), ("heads", "head_dim", "embed"), fan_in=h * dv, dtype=cfg.dtype),
    }


def mla_block(p: Params, x: jax.Array, cfg, *, positions: jax.Array,
              cache: Optional[dict] = None, cache_index=None,
              return_kv: bool = False, rows: CacheRows = CacheRows()):
    """Multi-head latent attention.  Cache holds the *latent* c_kv (dc) plus
    the shared rope key (dr) — ~10x smaller than full GQA KV: residency the
    bridge law pays for (§8 rule 4).

    Prefill (cache=None): decompress K/V per head; with return_kv=True the
    post-rope (c, k_pe) latents are returned for one-shot cache assembly.
    Decode (cache given): absorb W_uk into q and attend directly over the
    latent cache (the DeepSeek-V2 inference trick); ``rows`` says where the
    decoded rows sit in it (``CacheRows``).
    Returns (y, cache_or_latents_or_None).
    """
    b, s, d = x.shape
    h, dc, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dv = cfg.nope_head_dim, cfg.v_head_dim

    q = jnp.einsum("bsd,dhk->bshk", x, pvalue(p["wq"]))
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = jnp.einsum("bsd,dk->bsk", x, pvalue(p["wdkv"]))
    c, k_pe = ckv[..., :dc], ckv[..., dc:]
    c = apply_norm(p["kv_norm"], c, "rmsnorm")

    inv_freq, mscale = yarn_rope(cfg, dr)
    sin, cos = rope_table(positions, dr, cfg.rope_theta, inv_freq, mscale)
    q_pe = apply_rope(q_pe, sin, cos)
    k_pe = apply_rope(k_pe[:, :, None, :], sin, cos)[:, :, 0, :]  # shared across heads

    scale = mla_softmax_scale(cfg)
    if cache is not None:
        # decode (s=1, per-sequence index): write latents, absorbed attention
        idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
        cache = dict(cache, c=rows.write_at(cache["c"], idx, c[:, 0]),
                     k_pe=rows.write_at(cache["k_pe"], idx, k_pe[:, 0]))
        # absorbed decode: q_c = q_nope @ W_uk  -> score against latent cache
        # (TPU: bf16 cache operands + f32 accumulation — no f32 cache copy)
        q_c = jnp.einsum("bshn,chn->bshc", q_nope, pvalue(p["wuk"]))
        with jax.named_scope("KERNEL_mla_decode"):
            # the rows' latents, stacked as the scores read them (the TPU
            # compiler emits most of the stack's fusions with no source op)
            cc, ck = rows.read(cache["c"]), rows.read(cache["k_pe"])
            logits = (einsum_f32acc("bshc,btc->bhst", q_c, cc)
                      + einsum_f32acc("bshr,btr->bhst", q_pe, ck)) * scale
            tpos = jnp.arange(cc.shape[1])
            mask = tpos[None, :] <= idx[:, None]             # (b, T)
            logits = jnp.where(mask[:, None, None, :], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            o_c = einsum_f32acc("bhst,btc->bshc", probs, cc)
        out = jnp.einsum("bshc,chv->bshv", o_c,
                         pvalue(p["wuv"]).astype(jnp.float32)).astype(x.dtype)
        y = jnp.einsum("bshv,hvd->bsd", out, pvalue(p["wo"]))
        return y, cache

    # train / prefill: decompress per-head K/V, run the shared attention core
    k_nope = jnp.einsum("btc,chn->bthn", c, pvalue(p["wuk"]))
    v = jnp.einsum("btc,chv->bthv", c, pvalue(p["wuv"]))
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    # pad V to the score head dim for the shared core, then slice back
    out = attention_core(q_full, k_full,
                         jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv))),
                         impl=cfg.attn_impl, causal=True,
                         block_kv=cfg.attn_block_kv, scale=scale)[..., :dv]
    y = jnp.einsum("bshv,hvd->bsd", out, pvalue(p["wo"]))
    return y, ((c, k_pe) if return_kv else None)


# ---------------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {
            "wi": make_param(ks[0], (d, f), ("embed", "mlp"), fan_in=d, dtype=cfg.dtype),
            "wg": make_param(ks[1], (d, f), ("embed", "mlp"), fan_in=d, dtype=cfg.dtype),
            "wo": make_param(ks[2], (f, d), ("mlp", "embed"), fan_in=f, dtype=cfg.dtype),
        }
    return {
        "wi": make_param(ks[0], (d, f), ("embed", "mlp"), fan_in=d, dtype=cfg.dtype),
        "wo": make_param(ks[2], (f, d), ("mlp", "embed"), fan_in=f, dtype=cfg.dtype),
    }


def mlp_block(p: Params, x: jax.Array, cfg) -> jax.Array:
    if cfg.mlp_kind == "swiglu":
        return jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.silu(jnp.einsum("bsd,df->bsf", x, pvalue(p["wg"])))
            * jnp.einsum("bsd,df->bsf", x, pvalue(p["wi"])),
            pvalue(p["wo"]))
    h = jnp.einsum("bsd,df->bsf", x, pvalue(p["wi"]))
    if cfg.mlp_kind == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_kind == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(cfg.mlp_kind)
    return jnp.einsum("bsf,fd->bsd", h, pvalue(p["wo"]))


# ---------------------------------------------------------------------------------
# MoE: fine-grained experts, shared + routed top-k, capacity-based dispatch.
# Experts live on the "expert" logical axis (-> model mesh axis: EP);
# GSPMD materializes the token all-to-all from the sharding constraints.
# ---------------------------------------------------------------------------------

def init_moe(key, cfg) -> Params:
    """The router over all ``n_routed_experts`` and the weights of the
    experts held here (``cfg.experts_held``)."""
    d, f = cfg.d_model, cfg.d_expert
    e = cfg.experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": make_param(ks[0], (d, cfg.n_routed_experts), ("embed", "expert"),
                             fan_in=d, dtype=jnp.float32),
        "wi": make_param(ks[1], (e, d, f), ("expert", "embed", "mlp"), fan_in=d, dtype=cfg.dtype),
        "wg": make_param(ks[2], (e, d, f), ("expert", "embed", "mlp"), fan_in=d, dtype=cfg.dtype),
        "wo": make_param(ks[3], (e, f, d), ("expert", "mlp", "embed"), fan_in=f, dtype=cfg.dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.d_expert * cfg.n_shared_experts)
    return p


#: expert-parallel execution context, installed by the launcher alongside the
#: activation resolver: (mesh, data_axes, model_axis) or None for local runs
_MOE_CONTEXT: dict = {"mesh": None, "data_axes": (), "model_axis": None}


def set_moe_mesh(mesh, data_axes: tuple, model_axis: Optional[str]) -> None:
    _MOE_CONTEXT.update(mesh=mesh, data_axes=tuple(data_axes), model_axis=model_axis)


def _moe_expert_compute(x, idx, gates, wi, wg, wo, *, e_total: int,
                        capacity: int, dtype, e_offset) -> jax.Array:
    """Masked local expert compute for the experts this shard owns.

    x: (t, d) local tokens; idx/gates: (t, k) global expert assignment;
    wi/wg/wo: (e_loc, ...) local expert weights.  Returns the local experts'
    contribution to y (t, d) — summed across EP shards by the caller's psum.

    Dispatch is local, into (e_loc, capacity, d): under shard_map a
    single-device scatter and gather (no GSPMD partitioner involvement — the
    whole point of this structure; see EXPERIMENTS.md §Perf moe iteration).
    """
    t, d = x.shape
    e_loc, k = wi.shape[0], idx.shape[-1]

    local = (idx >= e_offset) & (idx < e_offset + e_loc)    # (t,k)
    lidx = jnp.where(local, idx - e_offset, 0)
    flat_lidx = lidx.reshape(t * k)
    flat_local = local.reshape(t * k)

    # slot position within each local expert (cumsum over assignment order)
    onehot = jax.nn.one_hot(lidx, e_loc, dtype=jnp.int32) * local[..., None]
    pos = jnp.cumsum(onehot.reshape(t * k, e_loc), axis=0) - 1
    pos = jnp.take_along_axis(pos, flat_lidx[:, None], axis=1)[:, 0]
    keep = flat_local & (pos < capacity)
    slot = jnp.where(keep, pos, capacity)                   # waste slot absorbs

    # a kept assignment owns its (expert, slot): scatter its token's index
    # there (``t``, a row of zeros, where none is kept) and gather the rows;
    # scattering the rows themselves takes the TPU compiler seconds a layer
    token = jnp.full((e_loc, capacity + 1), t, jnp.int32).at[
        flat_lidx, slot].set(jnp.where(keep, jnp.arange(t * k) // k, t))
    rows = jnp.concatenate([x.astype(dtype), jnp.zeros((1, d), dtype)])
    buf = rows[token[:, :capacity]]

    hg = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    hi = jnp.einsum("ecd,edf->ecf", buf, wi)
    out = jnp.einsum("ecf,efd->ecd", hg * hi, wo)

    out = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))            # waste slot reads 0
    gathered = out[flat_lidx, slot]                         # (t*k, d)
    w = (gates.reshape(t * k) * keep).astype(jnp.float32)
    y = jnp.sum((gathered.astype(jnp.float32) * w[:, None]).reshape(t, k, d), axis=1)
    return y


def moe_block(p: Params, x: jax.Array, cfg, *, capacity_factor: float = 1.25,
              no_drop: bool = False) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routed + shared experts.  Returns (y, aux_loss, routed).

    The router scores all ``n_routed_experts``; the routed part of ``y`` is
    that of the experts held here (``cfg.experts_held`` from
    ``cfg.expert_offset``: one chip's share under expert parallelism, whose
    other shares live on other chips), and the shared experts are added
    once.  ``routed`` (held,) int32 counts the assignments to each held
    expert.  The gates are the top-k softmax probabilities, rescaled to sum
    to one where ``cfg.norm_topk_prob``.

    Expert parallelism via shard_map over the model axis: activations are
    replicated across EP shards (they already are, in the TP layout), each
    shard scatters its assigned tokens into a *local* capacity buffer,
    computes its experts, and the shards' partial outputs are psum-combined —
    the EP analogue of Megatron's row-parallel all-reduce.  This deliberately
    bypasses GSPMD's scatter partitioner, which replicates dispatch buffers
    (measured: 72s memory / 254s collective terms vs 0.5s compute for
    deepseek-moe train_4k — see EXPERIMENTS.md §Perf).

    no_drop: capacity = local tokens (decode must be routing-exact).
    """
    g, s, d = x.shape
    e, k = cfg.n_routed_experts, cfg.moe_top_k
    wi, wg, wo = pvalue(p["wi"]), pvalue(p["wg"]), pvalue(p["wo"])
    held = wi.shape[0]

    with jax.named_scope("KERNEL_moe_route"):
        # the router in float32, as published (f32 weights and activations)
        gate_logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                                 pvalue(p["router"]),
                                 precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(gate_logits, axis=-1)
        gates, idx = lax.top_k(probs, k)                    # (g,s,k)
        if cfg.norm_topk_prob:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        routed = jnp.sum(idx.reshape(-1, 1) == cfg.expert_offset
                         + jnp.arange(held, dtype=idx.dtype), axis=0,
                         dtype=jnp.int32)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros(e).at[idx.reshape(-1)].add(1.0) / (g * s * k)
    aux = e * jnp.sum(me * ce)

    mesh = _MOE_CONTEXT["mesh"]
    model_axis = _MOE_CONTEXT["model_axis"]
    ep = mesh is not None and model_axis is not None and \
        held % mesh.shape[model_axis] == 0

    if not ep:
        t_loc = g * s
        capacity = t_loc if no_drop else int(
            max(k, math.ceil(t_loc * k / e * capacity_factor)))
        with jax.named_scope("KERNEL_moe_experts"):
            y = _moe_expert_compute(
                x.reshape(t_loc, d), idx.reshape(t_loc, k),
                gates.reshape(t_loc, k), wi, wg, wo, e_total=e,
                capacity=capacity, dtype=cfg.dtype, e_offset=cfg.expert_offset)
    else:
        from jax.sharding import PartitionSpec as P
        data_axes = _MOE_CONTEXT["data_axes"]
        dp = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
        n_model = mesh.shape[model_axis]
        n_data = 1
        for a in (data_axes or ()):
            n_data *= mesh.shape[a]
        g_loc = max(1, g // n_data) if g % n_data == 0 else g
        t_loc = g_loc * s
        capacity = t_loc if no_drop else int(
            max(k, math.ceil(t_loc * k / e * capacity_factor)))
        e_loc = held // n_model

        def body(x_blk, idx_blk, gates_blk, wi_l, wg_l, wo_l):
            gb = x_blk.shape[0]
            e_off = cfg.expert_offset + lax.axis_index(model_axis) * e_loc
            with jax.named_scope("KERNEL_moe_experts"):
                y_loc = _moe_expert_compute(
                    x_blk.reshape(gb * s, d), idx_blk.reshape(gb * s, k),
                    gates_blk.reshape(gb * s, k), wi_l, wg_l, wo_l,
                    e_total=e, capacity=capacity, dtype=cfg.dtype,
                    e_offset=e_off)
            y_loc = lax.psum(y_loc, model_axis)             # EP combine
            return y_loc.reshape(gb, s, d)

        xspec = P(dp, None, None)
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, xspec, xspec,
                      P(model_axis, None, None), P(model_axis, None, None),
                      P(model_axis, None, None)),
            out_specs=xspec,
        )(x, idx, gates, wi, wg, wo)

    y = y.astype(x.dtype).reshape(g, s, d)
    if cfg.n_shared_experts:
        y = y + mlp_block(p["shared"], x, cfg)
    return y, aux, routed


# ---------------------------------------------------------------------------------
# Sharding hint plumbing (resolved by shardings.py when a mesh is active)
# ---------------------------------------------------------------------------------

_ACTIVATION_RULES: dict[str, Any] = {"resolver": None}


def set_activation_resolver(fn) -> None:
    """Install a fn(logical_axes) -> sharding or None (launch/mesh wiring)."""
    _ACTIVATION_RULES["resolver"] = fn


def shard_hint(x: jax.Array, logical: tuple) -> jax.Array:
    resolver = _ACTIVATION_RULES["resolver"]
    if resolver is None:
        return x
    sharding = resolver(logical, tuple(x.shape))
    if sharding is None:
        return x
    return lax.with_sharding_constraint(x, sharding)
