"""Block-scale dequantization TPU kernel (DESIGN.md §13).

Restore crossings ship 1-byte codes plus one f32 scale per
``BLOCK_VALUES``-value block; this kernel widens them back on device:
``out = decode(codes) * scale``.  It exists so dequantization is a real,
executable *compute* stage — the serialized bridge never sees the widening,
only the wire bytes — and its cost model twin
(``ComputeModel.dequant_charge``) prices it as the HBM-bound elementwise
pass it is (read codes + scales, write f32; ~2 flops/value).

Codes are uint8 on the wire; the value decode is a bitcast — to int8 for
the int8 codec, to float8_e4m3fn for fp8 — followed by a widening multiply,
which is exactly the shape hardware dequant takes.  The (rows, 128) layout
puts the 128-value quant block on the lane dimension, matching the int8/fp8
min tile of (32, 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: grid tile: rows of quant blocks widened per step (lane dim is the
#: 128-value block itself)
ROW_TILE = 128


def _code_dtype(codec: str):
    if codec == "int8":
        return jnp.int8
    if codec == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown codec {codec!r}")


def _dequant_kernel(codes_ref, scales_ref, o_ref, *, codec: str):
    codes = codes_ref[...]
    vals = jax.lax.bitcast_convert_type(codes, _code_dtype(codec))
    o_ref[...] = vals.astype(jnp.float32) * scales_ref[...]


def dequant_kernel(codes: jax.Array, scales: jax.Array, *, codec: str,
                   interpret: bool = False) -> jax.Array:
    """codes: (nblocks, BLOCK) uint8; scales: (nblocks, 1) f32
    -> (nblocks, BLOCK) f32.  nblocks must be a multiple of ROW_TILE
    (the ops wrapper pads)."""
    nblocks, block = codes.shape
    kernel = functools.partial(_dequant_kernel, codec=codec)
    return pl.pallas_call(
        kernel,
        grid=(nblocks // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(codes, scales)
