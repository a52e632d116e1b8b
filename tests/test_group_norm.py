"""Fused GroupNorm kernel vs flax.linen.GroupNorm (interpret mode)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import group_norm as gn


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")


def _flax_gn(x, scale, bias, num_groups, relu):
    mod = nn.GroupNorm(num_groups=num_groups, epsilon=1e-6)
    y = mod.apply({"params": {"scale": scale, "bias": bias}}, x)
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("shape,groups", [
    ((2, 8, 8, 64), 32),
    ((3, 4, 4, 16), 8),
    ((2, 16, 32), 4),          # rank-3 input
])
@pytest.mark.parametrize("relu", [False, True])
def test_forward_matches_flax(shape, groups, relu):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    scale = jnp.asarray(rng.rand(shape[-1]) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(shape[-1]) * 0.1, jnp.float32)
    got = gn.fused_group_norm(x, scale, bias, groups, relu=relu)
    want = _flax_gn(x, scale, bias, groups, relu)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("relu", [False, True])
def test_gradients_match_flax(relu):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 4, 4, 32), jnp.float32)
    scale = jnp.asarray(rng.rand(32) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(32) * 0.1, jnp.float32)
    w = jnp.asarray(rng.randn(2, 4, 4, 32), jnp.float32)

    def loss_fused(x, s, b):
        return jnp.sum(gn.fused_group_norm(x, s, b, 8, relu=relu) * w)

    def loss_flax(x, s, b):
        return jnp.sum(_flax_gn(x, s, b, 8, relu) * w)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(loss_flax, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=3e-5, rtol=3e-4)


def test_bf16_activations_path():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 4, 4, 32), jnp.bfloat16)
    scale = jnp.ones((32,), jnp.float32)
    bias = jnp.zeros((32,), jnp.float32)
    y = gn.fused_group_norm(x, scale, bias, 8, relu=True)
    assert y.dtype == jnp.bfloat16
    want = _flax_gn(x.astype(jnp.float32), scale, bias, 8, True)
    np.testing.assert_allclose(
        y.astype(np.float32), want, atol=3e-2, rtol=3e-2
    )
    # bwd runs in bf16 too
    g = jax.grad(
        lambda x: jnp.sum(
            gn.fused_group_norm(x, scale, bias, 8, relu=True)
            .astype(jnp.float32)
        )
    )(x)
    assert g.dtype == jnp.bfloat16


def test_large_mean_variance_stability():
    # E[x^2]-mean^2 catastrophically cancels for |mean| >> std; the
    # kernel must use the centered two-pass variance.  (flax's own
    # GroupNorm computes E[x^2]-mean^2 and is off by ~350 on this
    # input, so the oracle here is float64 numpy, not flax.)
    rng = np.random.RandomState(5)
    x64 = rng.randn(2, 8, 8, 32) * 0.1 + 3000.0
    x = jnp.asarray(x64, jnp.float32)
    scale = jnp.ones((32,), jnp.float32)
    bias = jnp.zeros((32,), jnp.float32)
    xr = x64.reshape(2, -1, 8, 4)
    m = xr.mean(axis=(1, 3), keepdims=True)
    v = ((xr - m) ** 2).mean(axis=(1, 3), keepdims=True)
    truth = ((xr - m) / np.sqrt(v + 1e-6)).reshape(x64.shape)
    got = gn.fused_group_norm(x, scale, bias, 8)
    np.testing.assert_allclose(got, truth, atol=1e-2, rtol=1e-2)


def test_off_mode_matches(monkeypatch):
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 4, 4, 32), jnp.float32)
    scale = jnp.ones((32,), jnp.float32)
    bias = jnp.zeros((32,), jnp.float32)
    got = gn.fused_group_norm(x, scale, bias, 8)
    want = _flax_gn(x, scale, bias, 8, False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_under_jit_and_grad_composes():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 8, 8, 64), jnp.float32)
    scale = jnp.ones((64,), jnp.float32)
    bias = jnp.zeros((64,), jnp.float32)

    @jax.jit
    def step(x, s, b):
        return jax.value_and_grad(
            lambda x: jnp.sum(gn.fused_group_norm(x, s, b, 32,
                                                  relu=True) ** 2)
        )(x)

    v, g = step(x, scale, bias)
    assert np.isfinite(float(v))
    assert g.shape == x.shape
