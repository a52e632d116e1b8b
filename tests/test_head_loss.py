"""The head and the loss as one op (ops/head_loss.py) against the two
functions it replaces on the training path, ``next_token_loss(_head(...))``
and JAX's derivative of them."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.models import lora, transformer as tfm
from elasticdl_tpu.ops import head_loss as op
from elasticdl_tpu.ops.batch_shard import batch_axis


def _separate(params, hidden, tokens, cfg):
    """What ``model_spec``'s loss computed before the op."""
    return tfm.next_token_loss(tfm._head(params, hidden, cfg), tokens)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tied", [True, False])
def test_value_and_gradients_match_the_separate_functions(tied, dtype):
    """Value and the gradients to the hidden states, the head (or the
    tied embedding) and ``ln_f``; T = 13 is no multiple of 8 and the
    second example weighs zero, as a padded record does."""
    cfg = tfm.TransformerConfig(
        vocab_size=96, dim=32, num_heads=2, num_layers=1, max_seq_len=13,
        dtype=dtype, tied_embeddings=tied)
    rng = np.random.default_rng(7)
    compute = jnp.dtype(dtype)
    params = {
        "ln_f": jnp.asarray(1 + 0.1 * rng.standard_normal(32), compute),
        "embed" if tied else "lm_head": jnp.asarray(
            0.3 * rng.standard_normal((96, 32) if tied else (32, 96)),
            compute),
    }
    hidden = jnp.asarray(rng.standard_normal((3, 13, 32)), compute)
    tokens = jnp.asarray(rng.integers(0, 96, (3, 13)), jnp.int32)
    weights = jnp.asarray([1.0, 0.0, 1.0])

    def mean_of(loss):
        def f(params, hidden):
            per_example = loss(params, hidden, tokens, cfg)
            assert per_example.shape == (3,)
            assert per_example.dtype == jnp.float32
            return (per_example * weights).sum() / weights.sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    got, got_grads = mean_of(tfm.head_loss)(params, hidden)
    want, want_grads = mean_of(_separate)(params, hidden)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    # the same float32 arithmetic up to its order: in bfloat16 a last
    # place of a gradient may round the other way, 2^-8 of its value
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max()
    # the zero-weighted example's hidden states get no gradient at all
    assert not np.asarray(got_grads[1][1], np.float32).any()


def _train(spec, tokens, steps=4):
    params = spec.init_fn(jax.random.PRNGKey(0))
    opt_state = spec.optimizer.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(lambda p: spec.loss_fn(
            spec.apply_fn(p, tokens, True), tokens).mean())(params)
        updates, opt_state = spec.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("variant", ["dense", "moe", "lora"])
def test_model_spec_loss_trajectory_is_the_separate_functions(
        monkeypatch, variant):
    """A few optimizer steps through ``model_spec``'s training path give
    the losses they gave with ``next_token_loss(_head(...))``."""
    kwargs = dict(vocab_size=128, dim=32, num_heads=4, num_layers=2,
                  seq_len=16, dtype="float32", learning_rate=1e-2)
    if variant == "moe":
        kwargs.update(moe_experts=4, moe_top_k=2, tied_embeddings="false")
    build = (lambda: lora.model_spec(rank=4, **kwargs)) if (
        variant == "lora") else (lambda: tfm.model_spec(**kwargs))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, 128, (4, 16)), jnp.int32)
    got = _train(build(), tokens)
    monkeypatch.setattr(tfm, "head_loss", _separate)
    want = _train(build(), tokens)
    assert got[-1] < got[0]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_training_outputs_hold_no_logits_and_evaluation_still_does():
    spec = tfm.model_spec(vocab_size=64, dim=32, num_heads=2, num_layers=1,
                          seq_len=8, dtype="bfloat16")
    params = spec.init_fn(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 8), jnp.int32)
    assert set(spec.apply_fn(params, tokens, True)) == {
        "hidden", "params", "aux"}
    logits = spec.apply_fn(params, tokens, False)
    assert logits.shape == (2, 8, 64) and logits.dtype == jnp.float32


def test_one_log_line_per_compiled_shape():
    from elasticdl_tpu.ops.flash_attention import logger

    x = jnp.zeros((2, 24, 16), jnp.bfloat16)
    head = jnp.zeros((16, 40), jnp.bfloat16)
    tokens = jnp.zeros((2, 24), jnp.int32)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(handler)
    try:
        for _ in range(2):
            jax.jit(op.head_loss)(x, head, tokens)
        op.head_loss(x[:1], head, tokens[:1])
        # under the trainer's data axis, one shard's rows
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        with batch_axis(mesh, "data"):
            op.head_loss(x, head[:, :32], tokens)
    finally:
        logger.removeHandler(handler)
    assert [l for l in lines if l.startswith("head loss:")] == [
        "head loss: tokens=48 vocab=40 logits=bfloat16 bytes=3840",
        "head loss: tokens=24 vocab=40 logits=bfloat16 bytes=1920",
        "head loss: tokens=24 vocab=32 logits=bfloat16 bytes=1536"]
