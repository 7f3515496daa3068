"""The paper's workload: stacked-LSTM next-char model (tfjs lstm_text_generation).

Input: one-hot chars [B, sample_len, vocab] (the tfjs example feeds one-hot, no
embedding). Two stacked LSTM layers of ``cfg.d_model`` cells, dense softmax head
over the vocabulary, categorical cross-entropy on the next char. Keras/TF gate
order (i, f, c, o) and unit forget-gate bias, matching TensorFlow.js semantics.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, zeros


def init_lstm_model(key, cfg, vocab: int, dtype=jnp.float32):
    H = cfg.d_model
    n_layers = cfg.n_layers
    ks = jax.random.split(key, n_layers + 1)
    layers = []
    d_in = vocab
    for i in range(n_layers):
        kk, kr = jax.random.split(ks[i])
        # glorot for input kernel, orthogonal-ish (scaled normal) for recurrent
        kernel = dense_init(kk, (d_in + H, 4 * H), dtype,
                            scale=(2.0 / (d_in + 4 * H)) ** 0.5)
        bias = zeros((4 * H,), dtype)
        # unit forget bias (keras default)
        bias = bias.at[H:2 * H].set(1.0)
        layers.append({"kernel": kernel, "bias": bias})
        d_in = H
    head = {"w": dense_init(ks[-1], (H, vocab), dtype,
                            scale=(2.0 / (H + vocab)) ** 0.5),
            "b": zeros((vocab,), dtype)}
    return {"layers": layers, "head": head}


def lstm_cell(p, x, hc, *, use_pallas: bool = False, interpret: bool = False):
    """One step. x [B, d_in]; hc = (h [B,H], c [B,H])."""
    h, c = hc
    if use_pallas:
        from repro.kernels.ops import lstm_cell as pallas_cell
        return pallas_cell(x, h, c, p["kernel"], p["bias"], interpret=interpret)
    z = jnp.concatenate([x, h], axis=-1) @ p["kernel"] + p["bias"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    return h_new, c_new


def apply_lstm_model(params, onehot, *, use_pallas: bool = False,
                     interpret: bool = False):
    """onehot [B, T, V] -> next-char logits [B, V]."""
    B = onehot.shape[0]
    x_seq = jnp.moveaxis(onehot, 1, 0)                        # [T, B, V]
    for lp in params["layers"]:
        H = lp["kernel"].shape[1] // 4
        h0 = jnp.zeros((B, H), onehot.dtype)
        c0 = jnp.zeros((B, H), onehot.dtype)

        def step(hc, x):
            h_new, c_new = lstm_cell(lp, x, hc, use_pallas=use_pallas,
                                     interpret=interpret)
            return (h_new, c_new), h_new

        (_, _), hs = jax.lax.scan(step, (h0, c0), x_seq)
        x_seq = hs                                            # [T, B, H]
    last = x_seq[-1]                                          # [B, H]
    return last @ params["head"]["w"] + params["head"]["b"]


def lstm_loss(params, batch, *, use_pallas: bool = False, interpret: bool = False):
    """batch: {"x": one-hot [B,T,V], "y": int labels [B]} -> mean CE (nats)."""
    logits = apply_lstm_model(params, batch["x"], use_pallas=use_pallas,
                              interpret=interpret)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll)
