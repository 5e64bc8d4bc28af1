"""Plain building blocks of the reference models: float32, highest matmul
precision, no cache, no kernels, no batching.

The reference models (``configs/<name>.py``) are written from these and
import nothing of the program under test.  Every weight matmul goes through
a ``Matmul``: ``exact`` computes in float32 at the highest precision, and
``fp8`` rounds both operands to float8 e4m3 with a per-tensor scale first,
the lower precision the correctness control uses.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
#: largest finite float8 e4m3 value
E4M3_MAX = 448.0


def dense_init(key, shape, fan_in: int, dtype):
    """Normal(0, 1/fan_in) weights, drawn in float32 and stored in ``dtype``
    (the configuration's serving type)."""
    scale = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q, scale


class Matmul:
    """``a @ w`` over the last axis of ``a`` and the first of ``w``."""

    def __init__(self, kind: str = "exact"):
        if kind not in ("exact", "fp8"):
            raise ValueError(f"unknown matmul kind {kind!r}")
        self.kind = kind

    def __call__(self, a, w):
        w2 = w.reshape(w.shape[0], -1)
        if self.kind == "exact":
            out = jnp.dot(a.astype(F32), w2.astype(F32), precision=HIGHEST)
        else:
            qa, sa = _fp8(a)
            qw, sw = _fp8(w2)
            out = jnp.dot(qa, qw, precision=HIGHEST) * (sa * sw)
        return out.reshape(a.shape[:-1] + w.shape[1:])


def layer_norm(x, eps: float = 1e-6):
    """Layer norm without scale or bias."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def rms_norm(x, eps: float = 1e-6):
    """RMS norm with its scale at the initial value, one."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def rope(x, theta: float):
    """Rotary embedding, rotate-half form.  x: (S, heads, dim)."""
    s, _, dim = x.shape
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, window):
    """Softmax attention over earlier positions.  q: (S, H, D); k, v:
    (S, KV, D) with H a multiple of KV; ``window``: a scalar, a position
    attends to the last ``window`` positions (itself included)."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(d)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - window)
    logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)


def swiglu(mm: Matmul, x, wi, wg, wo):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)


def token_gaps(logits, tokens):
    """At each position, how far the logit of ``tokens[p]`` lies below the
    best logit there.  logits: (S, V) f32; tokens: (S,) int."""
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return logits.max(-1) - picked
