"""Plain reference of DeepSeek-V2-Lite (arXiv:2405.04434) as ``dsv2lite.json``
states it: one chip's share of an 8-way expert-parallel deployment.

Pre-norm decoder with RMS norms.  Attention is multi-head latent attention
without query compression, decompressed per head: the keys' no-rope part
and the values come up from the normed latent ``c``, one rope key is shared
by every head, and the rope dims are rotated at YaRN's frequencies (factor
40 over an original 4096 positions) with the softmax scaled by
``192^-0.5 * mscale(40, 0.707)^2``.  Layer 0 is a dense SwiGLU; every later
layer routes each token by a float32 softmax over all ``n_routed_experts``
and keeps the six most probable, whose probabilities are the gates (not
renormalised).  Only the experts held here (``n_experts_held`` from
``expert_offset``) add their gated output; the two shared experts always
add.  The output head is untied.  Float32 at the highest matmul precision
over one whole sequence: no cache, no batching, no capacity buffer, no
kernels.

Departure from the published model: the rope pairs are laid out as halves
(dims i and i + 32 rotate together), where the published code interleaves
them (dims 2i and 2i + 1).  The two are the same model under a fixed
permutation of the rope columns of ``wq`` and ``wdkv``, which random weights
do not see.

Weights are drawn from the seed by the recipe the configuration states:
every matrix Normal(0, 1/fan_in) in float32 (the router kept in float32,
the rest stored in the serving type), every norm scale one, with the key of
each drawn in the order below (``init``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import refcore as rc


def _attention_init(key, c: dict, dt) -> dict:
    d, h = c["d_model"], c["n_heads"]
    dc, dr = c["kv_lora_rank"], c["rope_head_dim"]
    dn, dv = c["nope_head_dim"], c["v_head_dim"]
    ks = jax.random.split(key, 6)
    return {
        "wq": rc.dense_init(ks[0], (d, h, dn + dr), d, dt),
        "wdkv": rc.dense_init(ks[1], (d, dc + dr), d, dt),
        "wuk": rc.dense_init(ks[3], (dc, h, dn), dc, dt),
        "wuv": rc.dense_init(ks[4], (dc, h, dv), dc, dt),
        "wo": rc.dense_init(ks[5], (h, dv, d), h * dv, dt),
    }


def _swiglu_init(key, d: int, f: int, dt) -> dict:
    ks = jax.random.split(key, 3)
    return {"wi": rc.dense_init(ks[0], (d, f), d, dt),
            "wg": rc.dense_init(ks[1], (d, f), d, dt),
            "wo": rc.dense_init(ks[2], (f, d), f, dt)}


def init(key, c: dict) -> dict:
    d, f, e = c["d_model"], c["d_expert"], c["n_experts_held"]
    dt = jnp.dtype(c["dtype"])
    ks = jax.random.split(key, 8)
    layer_keys = jax.random.split(ks[3], c["n_layers"])

    def block_keys(k):
        bk = jax.random.split(k, 10)
        return bk[1], bk[8]          # attention, MLP

    ak, mk = block_keys(layer_keys[0])
    block0 = {"attn": _attention_init(ak, c, dt),
              "mlp": _swiglu_init(mk, d, c["d_ff"], dt)}

    def moe_layer(k):
        ak, mk = block_keys(k)
        ek = jax.random.split(mk, 5)
        return {
            "attn": _attention_init(ak, c, dt),
            "router": rc.dense_init(ek[0], (d, c["n_routed_experts"]), d,
                                    jnp.float32),
            "wi": rc.dense_init(ek[1], (e, d, f), d, dt),
            "wg": rc.dense_init(ek[2], (e, d, f), d, dt),
            "wo": rc.dense_init(ek[3], (e, f, d), f, dt),
            "shared": _swiglu_init(ek[4], d, f * c["n_shared_experts"], dt),
        }

    layers = jax.vmap(moe_layer)(layer_keys[1:])
    return {"embed": rc.dense_init(ks[0], (c["vocab_size"], d), d, dt),
            "unembed": rc.dense_init(ks[2], (d, c["vocab_size"]), d, dt),
            "block0": block0, "layers": layers}


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_inv_freq(c: dict):
    """YaRN's rotary frequencies of the ``rope_head_dim`` dims: a pair keeps
    ``theta^(-2i/dim)`` below the fast correction dim, takes it divided by
    the factor above the slow one, and a linear blend between."""
    dim, base = c["rope_head_dim"], c["rope_theta"]
    factor, l0 = c["yarn_factor"], c["yarn_original_max_len"]

    def dim_of(rotations):
        return dim * math.log(l0 / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim_of(c["yarn_beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=rc.F32)
    extra = 1.0 / base ** (2 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def softmax_scale(c: dict) -> float:
    scale = 1.0 / math.sqrt(c["nope_head_dim"] + c["rope_head_dim"])
    return scale * _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"]) ** 2


def _rope(x, c: dict):
    """x: (S, heads, dim), pairs (i, i + dim/2)."""
    s, _, dim = x.shape
    ang = jnp.arange(s, dtype=rc.F32)[:, None] * yarn_inv_freq(c)
    m = (_mscale(c["yarn_factor"], c["yarn_mscale"])
         / _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"]))
    sin, cos = (m * jnp.sin(ang))[:, None, :], (m * jnp.cos(ang))[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, a, c: dict, mm: rc.Matmul):
    s = a.shape[0]
    h, dc = c["n_heads"], c["kv_lora_rank"]
    dn, dv = c["nope_head_dim"], c["v_head_dim"]
    q = mm(a, p["wq"])                                   # (S, h, dn + dr)
    ckv = mm(a, p["wdkv"])                               # (S, dc + dr)
    lat = rc.rms_norm(ckv[:, :dc])
    q_pe = _rope(q[..., dn:], c)
    k_pe = _rope(ckv[:, None, dc:], c)                   # one key, every head
    k = jnp.concatenate([mm(lat, p["wuk"]),
                         jnp.broadcast_to(k_pe, (s, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    v = mm(lat, p["wuv"])                                # (S, h, dv)
    logits = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=rc.HIGHEST) * softmax_scale(c)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=rc.HIGHEST)
    return mm(o.reshape(s, h * dv), p["wo"].reshape(h * dv, -1))


def _experts(p, m, c: dict, mm: rc.Matmul):
    """The held experts' gated outputs and the shared experts'."""
    probs = jax.nn.softmax(mm(m, p["router"]), -1)       # (S, E)
    gates, idx = lax.top_k(probs, c["moe_top_k"])        # (S, k)
    held = c["expert_offset"] + jnp.arange(p["wi"].shape[0])
    gate = jnp.sum(jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0),
                   axis=1)                               # (S, held)
    outs = jax.vmap(lambda wi, wg, wo: rc.swiglu(mm, m, wi, wg, wo))(
        p["wi"], p["wg"], p["wo"])                       # (held, S, d)
    shared = p["shared"]
    return (jnp.einsum("se,esd->sd", gate, outs, precision=rc.HIGHEST)
            + rc.swiglu(mm, m, shared["wi"], shared["wg"], shared["wo"]))


def forward(params, tokens, c: dict, mm: rc.Matmul):
    """tokens: (S,) int32 -> next-token logits (S, vocab) float32."""
    x = params["embed"][tokens].astype(rc.F32)
    b0 = params["block0"]
    x = x + _attention(b0["attn"], rc.rms_norm(x), c, mm)
    mlp = b0["mlp"]
    x = x + rc.swiglu(mm, rc.rms_norm(x), mlp["wi"], mlp["wg"], mlp["wo"])

    def layer(x, p):
        x = x + _attention(p["attn"], rc.rms_norm(x), c, mm)
        return x + _experts(p, rc.rms_norm(x), c, mm), None

    x, _ = lax.scan(layer, x, params["layers"])
    return mm(rc.rms_norm(x), params["unembed"])
