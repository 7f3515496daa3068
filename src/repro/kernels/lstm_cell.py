"""Fused LSTM cell — the paper's compute hot spot (2x50-cell stacked LSTM).

One kernel invocation computes, for a batch tile and a hidden tile:

    z = [x, h] @ W + b          (MXU: one [bT, Din+H] x [Din+H, 4*hT] matmul)
    c' = sigmoid(f) * c + sigmoid(i) * tanh(g)
    h' = sigmoid(o) * tanh(c')  (VPU: fused gate elementwise)

vs. the unfused path (tfjs semantics) which materializes z in HBM and
launches 6 elementwise kernels. The weight is laid out [Din+H, 4, Hp] so one
hidden tile covers all four gates of the same cells, keeping the gate
nonlinearity local to the block.

TPU notes: Mosaic splits the [bT, 4*hT] gate matmul into four gates only
when hT is a whole number of 128-lane tiles, so the wrapper pads every gate
to Hp = a multiple of ``block_h`` (the paper's H=50 becomes one 128-lane
tile); the padded cells compute zeros and are sliced off. All accumulation
is fp32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cell_kernel(xh_ref, w_ref, b_ref, c_ref, h_out_ref, c_out_ref):
    """Block shapes:
      xh [bT, Dxh]      (concatenated [x, h] tile — full feature dim)
      w  [Dxh, 4, hT]   b [4, hT]   c [bT, hT]
      out h/c [bT, hT]
    """
    xh = xh_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    dxh, _, ht = w.shape
    # one MXU matmul for all four gates of this tile
    z = jax.lax.dot_general(xh, w.reshape(dxh, 4 * ht),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    z = z.reshape(z.shape[0], 4, ht) + b[None]
    i, f, g, o = z[:, 0], z[:, 1], z[:, 2], z[:, 3]
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_h", "interpret"))
def lstm_cell(x, h, c, kernel, bias, *, block_b: int = 128,
              block_h: int = 128, interpret: bool = False):
    """x [B, Din]; h, c [B, H]; kernel [(Din+H), 4H]; bias [4H].

    ``block_h`` must be a multiple of 128 to compile for the TPU.
    Returns (h_new, c_new), matching ref.lstm_cell (keras gate order).
    """
    B, H = h.shape
    dxh = kernel.shape[0]
    Hp = pl.cdiv(H, block_h) * block_h
    pad = Hp - H
    w4 = jnp.pad(kernel.reshape(dxh, 4, H), ((0, 0), (0, 0), (0, pad)))
    b4 = jnp.pad(bias.reshape(4, H), ((0, 0), (0, pad)))
    cp = jnp.pad(c, ((0, 0), (0, pad)))
    xh = jnp.concatenate([x, h], axis=-1)

    bB = min(block_b, B)
    bH = block_h
    grid = (pl.cdiv(B, bB), Hp // bH)

    h_new, c_new = pl.pallas_call(
        _cell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, dxh), lambda ib, ih: (ib, 0)),
            pl.BlockSpec((dxh, 4, bH), lambda ib, ih: (0, 0, ih)),
            pl.BlockSpec((4, bH), lambda ib, ih: (0, ih)),
            pl.BlockSpec((bB, bH), lambda ib, ih: (ib, ih)),
        ],
        out_specs=[
            pl.BlockSpec((bB, bH), lambda ib, ih: (ib, ih)),
            pl.BlockSpec((bB, bH), lambda ib, ih: (ib, ih)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hp), h.dtype),
                   jax.ShapeDtypeStruct((B, Hp), c.dtype)],
        interpret=interpret,
    )(xh, w4, b4, cp)
    return h_new[:, :H], c_new[:, :H]
