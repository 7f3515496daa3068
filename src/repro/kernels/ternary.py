"""TernGrad compression kernel — the paper's §III bandwidth fix, fused.

The unfused path computes |g|, compares, signs, then packs in four separate
passes over the gradient. The kernel does threshold + sign + 2-bit packing
in one pass per block: read g once, write n/4 bytes once — exactly the
byte stream the DataServer/QueueServer wire protocol ships.

Encoding: {0 -> 0b00, +1 -> 0b01, -1 -> 0b10}, little-endian within the
byte, 4 values per uint8. Block = (rows of 4*lane) so each output byte's
4 inputs sit in one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_kernel(g_ref, s_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)                   # [rT, 4]
    s = s_ref[0]
    code = jnp.where(jnp.abs(g) >= s / 2,
                     jnp.where(g > 0, 1, 2), 0).astype(jnp.uint32)
    packed = (code[:, 0] | (code[:, 1] << 2) | (code[:, 2] << 4)
              | (code[:, 3] << 6))
    o_ref[...] = packed.astype(jnp.uint8)


def _decode_kernel(p_ref, s_ref, o_ref):
    packed = p_ref[...].astype(jnp.uint32)               # [rT]
    s = s_ref[0]
    parts = [(packed >> (2 * i)) & 3 for i in range(4)]
    code = jnp.stack(parts, axis=1)                      # [rT, 4]
    val = jnp.where(code == 1, 1.0, jnp.where(code == 2, -1.0, 0.0))
    o_ref[...] = (val * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ternary_encode(g_flat, scale, *, block_rows: int = 4096,
                   interpret: bool = False):
    """g_flat [N] (N % 4 == 0), scale scalar fp32 -> packed uint8 [N/4]."""
    n = g_flat.shape[0]
    assert n % 4 == 0, n
    rows = n // 4
    bR = min(block_rows, rows)
    g2 = g_flat.reshape(rows, 4)
    return pl.pallas_call(
        _encode_kernel,
        grid=(pl.cdiv(rows, bR),),
        in_specs=[pl.BlockSpec((bR, 4), lambda r: (r, 0)),
                  pl.BlockSpec((1,), lambda r: (0,))],
        out_specs=pl.BlockSpec((bR,), lambda r: (r,)),
        out_shape=jax.ShapeDtypeStruct((rows,), jnp.uint8),
        interpret=interpret,
    )(g2, scale.reshape(1))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ternary_decode(packed, scale, *, block_rows: int = 4096,
                   interpret: bool = False):
    """packed uint8 [N/4], scale scalar -> g_flat fp32 [N]."""
    rows = packed.shape[0]
    bR = min(block_rows, rows)
    out = pl.pallas_call(
        _decode_kernel,
        grid=(pl.cdiv(rows, bR),),
        in_specs=[pl.BlockSpec((bR,), lambda r: (r,)),
                  pl.BlockSpec((1,), lambda r: (0,))],
        out_specs=pl.BlockSpec((bR, 4), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 4), jnp.float32),
        interpret=interpret,
    )(packed, scale.reshape(1))
    return out.reshape(rows * 4)
