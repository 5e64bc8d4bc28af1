"""Paged-attention decode TPU kernel.

One new token per sequence attends over that sequence's KV pages.  Pages are
physically scattered in the pool; the per-sequence page list (block table)
rides in scalar-prefetch memory so the BlockSpec index_map can steer each
grid step's DMA to the right page — data-dependent addressing without any
gather materialization (the TPU-native replacement for vLLM's CUDA paged
attention; see DESIGN.md §2).

Grid: (batch, max_pages) with the page dimension sequential; online-softmax
state (m, l, acc) persists in VMEM scratch across a sequence's pages.  Pages
past ceil(len/page) are skipped entirely via pl.when — short sequences cost
only their own length.  GQA is computed in-register: q is viewed as
(KV, H/KV, D) against the page's (page, KV, D) keys.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(block_tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, h: int, kv: int, d: int,
                  scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < length)
    def _compute():
        rep = h // kv
        q = q_ref[0].astype(jnp.float32) * scale              # (h, d)
        k = k_ref[0].astype(jnp.float32)                      # (page, kv, d)
        v = v_ref[0].astype(jnp.float32)
        qg = q.reshape(kv, rep, d)
        # scores: (kv, rep, page)
        s = jax.lax.dot_general(qg, k.transpose(1, 2, 0),
                                (((2,), (1,)), ((0,), (0,))))
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (kv, rep, page), 2)
        s = jnp.where(pos < length, s, NEG_INF)
        s = s.reshape(h, page)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (h, page)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.reshape(kv, rep, page), v.transpose(1, 0, 2),
            (((2,), (1,)), ((0,), (0,))))                     # (kv, rep, d)
        acc_scr[...] = acc_scr[...] * corr + pv.reshape(h, d)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, block_tables, lengths, *,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pages/v_pages: (P, page, KV, D);
    block_tables: (B, pages_max) i32; lengths: (B,) i32 -> (B, H, D)."""
    b, h, d = q.shape
    n_pages, page, kv, _ = k_pages.shape
    pages_max = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_kernel, page=page, h=h, kv=kv, d=d,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_max),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, j, bt, ln: (b_, 0, 0)),
            pl.BlockSpec((1, page, kv, d),
                         lambda b_, j, bt, ln: (bt[b_, j], 0, 0, 0)),
            pl.BlockSpec((1, page, kv, d),
                         lambda b_, j, bt, ln: (bt[b_, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, j, bt, ln: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)
