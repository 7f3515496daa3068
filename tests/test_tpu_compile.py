"""Compile-only tests for one TPU v5e chip, at real widths.

Nothing runs: each program is lowered and compiled for a v5e chip that is
described, not attached, so what the chip's compiler would refuse (a
Mosaic layout it cannot lower, a primitive with no TPU rule) fails here.
The topology is described only inside a fixture, and only the worker that
runs this file loads the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as FA
from repro.kernels import lstm_cell as LC
from repro.kernels import rmsnorm as RN
from repro.kernels import ternary as TN


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text           # the Pallas kernel is there


@pytest.mark.parametrize("B,Din,H", [(8, 38, 50), (8, 512, 512)],
                         ids=["paper", "h512"])
def test_lstm_cell_compiles(one_chip, B, Din, H):
    s = lambda *shape: _spec(one_chip, shape)
    _compile_kernel(lambda *a: LC.lstm_cell(*a, interpret=False),
                    s(B, Din), s(B, H), s(B, H), s(Din + H, 4 * H), s(4 * H))


def test_rmsnorm_compiles(one_chip):
    _compile_kernel(lambda x, w: RN.rmsnorm(x, w, interpret=False),
                    _spec(one_chip, (4096, 2048), jnp.bfloat16),
                    _spec(one_chip, (2048,), jnp.bfloat16))


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 2048, 4, 128), jnp.bfloat16)
    _compile_kernel(lambda q, k, v: FA.flash_attention(q, k, v,
                                                       interpret=False),
                    q, kv, kv)


def test_ternary_compiles(one_chip):
    n = 65536
    scale = _spec(one_chip, ())
    _compile_kernel(lambda g, s: TN.ternary_encode(g, s, interpret=False),
                    _spec(one_chip, (n,)), scale)
    _compile_kernel(lambda p, s: TN.ternary_decode(p, s, interpret=False),
                    _spec(one_chip, (n // 4,), jnp.uint8), scale)


@pytest.fixture(scope="module")
def paper_problem():
    from repro.core.mapreduce import TrainingProblem
    from repro.data.text import synthetic_corpus
    return TrainingProblem.paper_problem(
        seed=0, corpus=synthetic_corpus(50_000, seed=0))


def _on_chip(tree, sharding):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


def test_paper_grad_step_compiles(one_chip, paper_problem):
    """The volunteer's value_and_grad at the published 2x50 width."""
    p = paper_problem
    assert (p.cfg.d_model, p.cfg.vocab) == (50, 38)
    batch = p.minibatch(0, 0)
    p._grad_fn.lower(_on_chip(p.params0, one_chip),
                     _on_chip(batch, one_chip)).compile()


def test_paper_applier_scan_compiles(one_chip, paper_problem):
    """The applier's donated scan over a drain of 16 flat gradient rows."""
    p = paper_problem
    carry = jax.eval_shape(p.flat_carry, p.params0, p.opt_state0)
    rows = _spec(one_chip, (16, carry[0].shape[0]))
    jax.jit(p.apply_batch_flat).lower(_on_chip(carry, one_chip),
                                      rows).compile()


def test_paper_version_pack_compiles(one_chip, paper_problem):
    """The program that packs row i of a drain of 16 into the one buffer a
    fetched version is copied to the host in."""
    p = paper_problem
    carry = jax.eval_shape(p.flat_carry, p.params0, p.opt_state0)
    steps = jax.tree.map(lambda x: _spec(one_chip, (16,) + x.shape, x.dtype),
                         carry)
    out = p._pack_step_fn.lower(steps, _spec(one_chip, (), jnp.int32))
    assert out.out_info.shape == (2 * carry[0].shape[0] + 1,)
    out.compile()
