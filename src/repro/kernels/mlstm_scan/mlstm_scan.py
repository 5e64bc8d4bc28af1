"""Chunked mLSTM TPU kernel (xLSTM matrix-memory recurrence).

The mLSTM recurrence C_t = f_t C_{t-1} + i_t v_t k_t^T is a gated linear
attention; its chunkwise-parallel form does (chunk x chunk) MXU work inside
a chunk plus an O(dk x dv) state hand-off between chunks.  Grid:
(batch, heads, chunks) with the chunk dimension sequential — the state
(C, n, m) lives in VMEM scratch across chunk iterations, so only q/k/v/gates
stream from HBM and only y streams back: exactly the byte profile the
dry-run's kernel substitution credits the SSM archs with.

All intra-chunk decay/stabilizer tensors (D, m_t) stay in registers/VMEM.
Numerics: f32 throughout the recurrence (bf16 in/out), matching ref.py's
stabilized exponential gating.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, y_ref,
                  C_scr, n_scr, m_scr, *, chunk: int, dk: int, dv: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    q = q_ref[0, :, 0, :].astype(jnp.float32)          # (chunk, dk), pre-scaled
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)          # (chunk, dv)
    li = li_ref[0, :, 0].astype(jnp.float32)           # (chunk,)
    lf = lf_ref[0, :, 0].astype(jnp.float32)

    F = jnp.cumsum(lf)                                  # (chunk,)
    m_prev = m_scr[0, 0]
    # intra-chunk log decay D[t, s] = F_t - F_s + li_s  (s <= t)
    Dmat = F[:, None] - F[None, :] + li[None, :]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Dmat = jnp.where(tpos >= spos, Dmat, NEG_INF)
    m_inter = m_prev + F                                # (chunk,)
    m_t = jnp.maximum(Dmat.max(axis=1), m_inter)
    intra_w = jnp.exp(Dmat - m_t[:, None])              # (t, s)
    inter_w = jnp.exp(m_inter - m_t)                    # (t,)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (t, s)
    wscores = scores * intra_w
    intra = jax.lax.dot_general(wscores, v, (((1,), (0,)), ((), ())))
    inter = jax.lax.dot_general(q * inter_w[:, None], C_scr[...],
                                (((1,), (0,)), ((), ())))
    num = intra + inter

    norm_vec = jax.lax.dot_general(intra_w, k, (((1,), (0,)), ((), ())))  # (t, dk)
    qdotn = jnp.sum(q * norm_vec, axis=1) + \
        (q * inter_w[:, None]) @ n_scr[:, 0]
    denom = jnp.maximum(jnp.abs(qdotn), jnp.exp(-m_t))
    y_ref[0, :, 0, :] = (num / denom[:, None]).astype(y_ref.dtype)

    # state hand-off to the next chunk
    F_tot = F[-1]
    m_new = jnp.maximum(m_prev + F_tot, (F_tot - F + li).max())
    w_carry = jnp.exp(m_prev + F_tot - m_new)
    kv_w = jnp.exp(F_tot - F + li - m_new)              # (chunk,)
    C_scr[...] = C_scr[...] * w_carry + jax.lax.dot_general(
        k * kv_w[:, None], v, (((0,), (0,)), ((), ())))
    n_scr[...] = n_scr[...] * w_carry + (
        (k * kv_w[:, None]).sum(axis=0))[:, None]
    m_scr[...] = jnp.full_like(m_scr, m_new)


def mlstm_scan_kernel(q, k, v, log_i, log_f, *, chunk: int = 128,
                      interpret: bool = False):
    """q,k: (B,S,H,dk) pre-scaled; v: (B,S,H,dv); gates (B,S,H) (log-space).
    Returns y: (B,S,H,dv)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        log_i = jnp.pad(log_i, ((0, 0), (0, pad), (0, 0)), constant_values=NEG_INF)
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_mlstm_kernel, chunk=c, dk=dk, dv=dv)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, c, 1, dk), lambda b_, h_, i: (b_, i, h_, 0)),
            pl.BlockSpec((1, c, 1, dk), lambda b_, h_, i: (b_, i, h_, 0)),
            pl.BlockSpec((1, c, 1, dv), lambda b_, h_, i: (b_, i, h_, 0)),
            pl.BlockSpec((1, c, 1), lambda b_, h_, i: (b_, i, h_)),
            pl.BlockSpec((1, c, 1), lambda b_, h_, i: (b_, i, h_)),
        ],
        out_specs=pl.BlockSpec((1, c, 1, dv), lambda b_, h_, i: (b_, i, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nc * c, h, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_i, log_f)
    return y[:, :s]
