"""jit'd public wrapper for the flash-attention kernel.

On TPU backends this dispatches to the compiled Pallas kernel.  Elsewhere
it runs the jnp oracle, or, with ``force_kernel``, the kernel in interpret
mode (the CPU tests compare the two).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .ref import attention_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "force_kernel"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_kv: int = 128, force_kernel: bool = False):
    """Public API.  q: (B, Sq, H, D); k, v: (B, Sk, KV, D)."""
    if _on_tpu() or force_kernel:
        return flash_attention_kernel(
            q, k, v, causal=causal, window=window, block_q=block_q,
            block_kv=block_kv, interpret=not _on_tpu())
    return attention_ref(q, k, v, causal=causal, window=window)
