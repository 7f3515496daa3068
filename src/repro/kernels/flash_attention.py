"""Flash attention (GQA, causal/sliding-window) as a Pallas TPU kernel.

Adaptation of the paper-era WebGL "shader" idea to the TPU memory hierarchy:
instead of materializing [Sq, Skv] scores in HBM, each grid cell owns one
(batch, kv-head, q-tile) and streams kv tiles HBM->VMEM, carrying the online
softmax (m, l, acc) in VMEM scratch. MXU does the two matmuls per tile;
the rescaling is VPU work. Tiles are 128-aligned for the MXU.

Grid: (B, Kv, Sq/blk_q); the kv loop is a fori_loop inside the kernel with
a causal early-exit bound, so the quadratic term only pays for the lower
triangle. GQA is handled by folding the G = H/Kv group dim into the q tile
rows ([G * blk_q, hd] q block per kv head; blk_q a multiple of 8 keeps the
fold a tile-aligned reshape on the TPU).

Forward-only: training uses the jnp flash path (layers.flash_attention,
custom_vjp); this kernel is the serving/prefill fast path. Validated in
interpret mode against ref.flash_attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_q: int, blk_k: int,
               seq_kv: int, causal: bool, window: int, scale: float):
    """Block shapes (leading B/Kv dims are size-1 grid blocks):
      q [1, 1, G, blk_q, hd] -> folded to [G*blk_q, hd] (row r is query
                                position q_start + r % blk_q)
      k [1, 1, Skv, hd]   v [1, 1, Skv, hd]   (full kv row of this head in
                                              VMEM; the loop reads blk_k
                                              tiles with pl.ds)
      o [1, 1, G, blk_q, hd]
    """
    iq = pl.program_id(2)
    G, bq, hd = q_ref.shape[2:]
    rows = G * bq
    q2 = q_ref[0, 0].astype(jnp.float32).reshape(rows, hd) * scale

    q_start = iq * blk_q
    # causal upper bound on kv tiles this q tile can see
    if causal:
        hi = jnp.minimum(seq_kv, q_start + blk_q)
    else:
        hi = seq_kv
    n_tiles = pl.cdiv(hi, blk_k)
    qpos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (G, bq, blk_k), 1).reshape(rows, blk_k)

    def body(t, carry):
        m, l, acc = carry
        k_start = pl.multiple_of(t * blk_k, blk_k)
        k = k_ref[0, 0, pl.ds(k_start, blk_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k_start, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # mask: causal + sliding window + kv-padding
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, blk_k), 1)
        ok = kpos < seq_kv
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    a0 = jnp.zeros((rows, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(G, bq, hd)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False):
    """q [B,Sq,H,hd]; k/v [B,Skv,Kv,hd]; GQA G=H/Kv. Self-attention with
    q aligned to the end of kv (training/prefill: Sq == Skv)."""
    B, Sq, H, hd = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    assert Sq == Skv, "kernel assumes aligned self-attention"
    scale = 1.0 / math.sqrt(hd)

    bq = min(blk_q, Sq)
    pad_q = pl.cdiv(Sq, bq) * bq - Sq
    bk = min(blk_k, Skv)
    pad_k = pl.cdiv(Skv, bk) * bk - Skv

    # layout: q [B, Kv, G, Sq, hd]; kv [B, Kv, Skv, hd]
    qr = jnp.transpose(q.reshape(B, Sq, Kv, G, hd), (0, 2, 3, 1, 4))
    kr = jnp.moveaxis(k, 2, 1)
    vr = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k

    out = pl.pallas_call(
        functools.partial(_fa_kernel, blk_q=bq, blk_k=bk, seq_kv=Skv,
                          causal=causal, window=window, scale=scale),
        grid=(B, Kv, Sq_p // bq),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, hd), lambda b, h, i: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, Skv_p, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Skv_p, hd), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, hd),
                               lambda b, h, i: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, Sq_p, hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    out = out[:, :, :, :Sq]                             # strip q padding
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, H, hd)
