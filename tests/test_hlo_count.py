"""Weighted HLO analyzer vs closed-form costs (loop-aware counting)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_count import weighted_cost


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


def test_plain_matmul_flops():
    M, K, N = 128, 256, 512
    c = _compile(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32),
    )
    wc = weighted_cost(c.as_text())
    assert wc.flops == 2 * M * K * N
    assert wc.flops == c.cost_analysis()["flops"]  # loop-free: agree


def test_scan_flops_multiplied_by_trip():
    T, B, D = 7, 8, 64

    def g(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, params)
        return c.sum()

    c = _compile(
        g,
        jax.ShapeDtypeStruct((T, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32),
    )
    wc = weighted_cost(c.as_text())
    assert wc.flops == T * 2 * B * D * D
    assert dict(wc.loops)  # at least one loop with trip T
    assert max(t for _, t in wc.loops) == T


def test_grad_of_scan_triples_flops():
    T, B, D = 5, 4, 32

    def g(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, params)
        return c.sum()

    c = _compile(
        jax.grad(g),
        jax.ShapeDtypeStruct((T, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32),
    )
    wc = weighted_cost(c.as_text())
    assert wc.flops == pytest.approx(3 * T * 2 * B * D * D, rel=0.05)


def test_nested_scan():
    T, inner, B, D = 6, 3, 4, 16

    def h(params, x):
        def outer(c, w):
            def in_body(ci, _):
                return jnp.tanh(ci @ w), None
            ci, _ = jax.lax.scan(in_body, c, None, length=inner)
            return ci, None
        c, _ = jax.lax.scan(outer, x, params)
        return c.sum()

    c = _compile(
        h,
        jax.ShapeDtypeStruct((T, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32),
    )
    wc = weighted_cost(c.as_text())
    assert wc.flops == T * inner * 2 * B * D * D


def test_bytes_scale_with_trip():
    T, B, D = 9, 8, 32

    def g(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, params)
        return c.sum()

    def g1(params, x):  # single iteration for comparison
        return jnp.tanh(x @ params[0]).sum()

    cT = _compile(g, jax.ShapeDtypeStruct((T, D, D), jnp.float32),
                  jax.ShapeDtypeStruct((B, D), jnp.float32))
    c1 = _compile(g1, jax.ShapeDtypeStruct((T, D, D), jnp.float32),
                  jax.ShapeDtypeStruct((B, D), jnp.float32))
    bT = weighted_cost(cT.as_text()).bytes_accessed
    b1 = weighted_cost(c1.as_text()).bytes_accessed
    assert bT > 0.7 * T * b1  # body bytes scale ~linearly with trips
