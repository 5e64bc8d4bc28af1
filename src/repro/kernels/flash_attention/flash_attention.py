"""Flash attention TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

Canonical online-softmax structure: grid (batch, q_heads, q_blocks,
kv_blocks) with the kv dimension "arbitrary" (sequential) — running max /
sum / accumulator live in VMEM scratch and persist across kv iterations, so
the (bq x bk) logits tile never touches HBM.  That is precisely the traffic
the dry-run's kernel-substituted memory term credits (launch/dryrun.py).

The wrapper lays the operands out head-major, (B, H, S, D), so each block's
last two dimensions are (rows, d): the TPU tiling the compiler requires.
GQA is native: the BlockSpec index map selects kv head h * KV // H, so K/V
are never repeated in memory.  Causal + sliding-window masks are applied
in-register; fully-masked kv blocks are skipped via pl.when on the block
index (upper-triangle blocks cost zero MXU work).

Block sizes default to MXU/VREG-aligned (128) and are swept in tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: Optional[int], bq: int, bk: int,
                  sk: int, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = kj * bk

    # block-level skip: in causal mode, blocks entirely above the diagonal
    # (and, with a window, entirely below it) do no work at all
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, "GQA needs H % KV == 0"
    bq = min(block_q, sq)
    bk = min(block_kv, sk)
    nq = -(-sq // bq)
    nk = -(-sk // bk)
    pad_q = nq * bq - sq
    pad_k = nk * bk - sk
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, bq=bq, bk=bk, sk=sk,
        scale=1.0 / math.sqrt(d))

    def kv_index(b_, h_, i, j):
        return (b_, h_ * kv // h, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * bq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq].transpose(0, 2, 1, 3)
