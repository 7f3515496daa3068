"""The loop-aware HLO cost analyzer against programs with KNOWN costs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_scan_flops_scaled_by_trip_count():
    N, T = 128, 8

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=T)
        return y

    txt = _compile_text(f, jax.ShapeDtypeStruct((N, N), jnp.float32),
                        jax.ShapeDtypeStruct((N, N), jnp.float32))
    res = hlo_cost.analyze(txt)
    expect = T * 2 * N ** 3
    assert res["flops"] == pytest.approx(expect, rel=0.01), \
        (res["flops"], expect)
    assert any(t == T for _, t in res["while_loops"])


def test_nested_scan_multiplies():
    N, T1, T2 = 64, 3, 5

    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=T2)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=T1)
        return y

    txt = _compile_text(f, jax.ShapeDtypeStruct((N, N), jnp.float32),
                        jax.ShapeDtypeStruct((N, N), jnp.float32))
    res = hlo_cost.analyze(txt)
    expect = T1 * T2 * 2 * N ** 3
    assert res["flops"] == pytest.approx(expect, rel=0.01)


def test_unrolled_matches_xla_cost_analysis():
    N = 96

    def f(x, w):
        for _ in range(4):
            x = x @ w
        return x

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((N, N), jnp.float32),
                               jax.ShapeDtypeStruct((N, N), jnp.float32))
    compiled = lowered.compile()
    ours = hlo_cost.analyze(compiled.as_text())["flops"]
    xla = float(compiled.cost_analysis().get("flops", 0))
    assert ours == pytest.approx(xla, rel=0.01) == pytest.approx(
        4 * 2 * N ** 3, rel=0.01)


def test_dot_general_batched_flops():
    B, M, K, N = 4, 32, 48, 16

    def f(a, b):
        return jnp.einsum("bmk,bkn->bmn", a, b)

    txt = _compile_text(f, jax.ShapeDtypeStruct((B, M, K), jnp.float32),
                        jax.ShapeDtypeStruct((B, K, N), jnp.float32))
    res = hlo_cost.analyze(txt)
    assert res["flops"] == pytest.approx(2 * B * M * K * N, rel=0.01)


def test_collective_bytes_counted_with_loop_scaling():
    """Hand-written module: an all-reduce inside a trip-8 while loop."""
    txt = """
HloModule test

%body (p: (s32[], f32[64,4])) -> (s32[], f32[64,4]) {
  %p = (s32[], f32[64,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,4] get-tuple-element(%p), index=1
  %ar = f32[64,4] all-reduce(%x), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,4]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[64,4])) -> pred[] {
  %p = (s32[], f32[64,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[64,4]) -> f32[64,4] {
  %a = f32[64,4] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[64,4]) tuple(%z, %a)
  %w = (s32[], f32[64,4]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"8"}}
  ROOT %out = f32[64,4] get-tuple-element(%w), index=1
}
"""
    res = hlo_cost.analyze(txt)
    assert res["collective_bytes"]["all-reduce"] == 8 * 64 * 4 * 4
    assert res["collective_bytes"]["total"] == 8 * 64 * 4 * 4


def test_shape_bytes_parser():
    assert hlo_cost.shape_bytes("f32[128,64]{1,0}") == 128 * 64 * 4
    assert hlo_cost.shape_bytes("bf16[10]") == 20
    assert hlo_cost.shape_bytes("(f32[4], s32[2])") == 24
    assert hlo_cost.shape_bytes("pred[]") == 1   # scalars: dims product = 1
