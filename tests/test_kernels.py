"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the compiled kernels are checked in test_tpu_compile.py and chip_smoke.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Din,H", [(1, 7, 5), (8, 96, 50), (16, 128, 128),
                                     (5, 33, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell_sweep(B, Din, H, dtype):
    k = jax.random.PRNGKey(B * 1000 + Din)
    ks = jax.random.split(k, 5)
    x = jax.random.normal(ks[0], (B, Din), dtype)
    h = jax.random.normal(ks[1], (B, H), dtype)
    c = jax.random.normal(ks[2], (B, H), dtype)
    W = (jax.random.normal(ks[3], (Din + H, 4 * H)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[4], (4 * H,)) * 0.1).astype(dtype)
    h1, c1 = ops.lstm_cell(x, h, c, W, b, interpret=True)
    h2, c2 = ref.lstm_cell(x, h, c, W, b)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", [(4, 64), (2, 17, 256), (1, 3, 5, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
    y1 = ops.rmsnorm(x, s, interpret=True)
    y2 = ref.rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,Kv,hd", [
    (1, 64, 4, 4, 32),      # MHA
    (2, 129, 8, 4, 64),     # GQA, ragged seq
    (1, 200, 8, 1, 16),     # MQA
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 37), (False, 0)])
def test_flash_attention_sweep(B, S, H, Kv, hd, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, Kv, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, Kv, hd)) * 0.5
    o1 = ops.flash_attention(q, k, v, causal=causal, window=window,
                             blk_q=64, blk_k=64, interpret=True)
    o2 = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=5e-5, atol=5e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtype(dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = (jax.random.normal(ks[0], (2, 64, 4, 32)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (2, 64, 2, 32)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (2, 64, 2, 32)) * 0.5).astype(dtype)
    o1 = ops.flash_attention(q, k, v, interpret=True)
    o2 = ref.flash_attention(q, k, v)
    assert o1.dtype == dtype
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n", [4, 128, 4096, 10000])
def test_ternary_kernel_roundtrip(n):
    n4 = (n + 3) // 4 * 4
    g = jax.random.normal(jax.random.PRNGKey(n), (n4,))
    s = jnp.max(jnp.abs(g))
    packed = ops.ternary_encode(g, s, interpret=True)
    assert packed.dtype == jnp.uint8 and packed.shape == (n4 // 4,)
    dec = ops.ternary_decode(packed, s, interpret=True)
    t = ref.ternary_encode(g, s).astype(jnp.float32) * s
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(t))
    # ref-level pack/unpack agrees with the kernel bytes
    np.testing.assert_array_equal(
        np.asarray(packed), np.asarray(ref.ternary_pack(ref.ternary_encode(g, s))))


def test_lstm_model_pallas_path_matches_jnp():
    """The full paper model with use_pallas=True equals the jnp path."""
    from repro.models import lstm as LSTM
    import repro.configs as C
    cfg = C.get("paper-lstm").replace(vocab=64)
    params = LSTM.init_lstm_model(jax.random.PRNGKey(0), cfg, 64)
    x = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(1), (4, 20), 0, 64), 64)
    batch = {"x": x, "y": jnp.zeros((4,), jnp.int32)}
    l1 = LSTM.lstm_loss(params, batch, use_pallas=False)
    l2 = LSTM.lstm_loss(params, batch, use_pallas=True, interpret=True)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


@pytest.mark.parametrize("name", ["lstm_cell", "rmsnorm", "flash_attention",
                                  "ternary_encode", "ternary_decode"])
def test_compiled_kernel_refuses_cpu(name):
    """Kernels default to compiled: off the TPU, a call that does not ask
    for interpret mode fails loudly instead of interpreting silently."""
    assert jax.default_backend() == "cpu"
    f32 = jnp.ones
    args = {
        "lstm_cell": (f32((8, 16)), f32((8, 8)), f32((8, 8)), f32((24, 32)),
                      f32((32,))),
        "rmsnorm": (f32((8, 128)), f32((128,))),
        "flash_attention": (f32((1, 64, 2, 32)),) * 3,
        "ternary_encode": (f32((64,)), jnp.float32(1.0)),
        "ternary_decode": (jnp.zeros((16,), jnp.uint8), jnp.float32(1.0)),
    }[name]
    with pytest.raises(ValueError, match="interpret"):
        getattr(ops, name)(*args)
