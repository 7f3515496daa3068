"""Fused RMSNorm kernel (every transformer block runs it twice per layer).

Row-tile kernel: each block normalizes [rT, D] rows entirely in VMEM —
one read of x, one write of y, vs. the unfused mean/rsqrt/mul chain which
round-trips x three times. fp32 math inside regardless of storage dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * s_ref[...].astype(jnp.float32)[None]
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x [..., D]; scale [D]. Returns RMS-normalized x (same dtype)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    bR = min(block_rows, R)

    y = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pl.cdiv(R, bR),),
        in_specs=[pl.BlockSpec((bR, D), lambda r: (r, 0)),
                  pl.BlockSpec((D,), lambda r: (0,))],
        out_specs=pl.BlockSpec((bR, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(xf, scale)
    return y.reshape(orig_shape)
