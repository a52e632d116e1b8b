"""Multi-token prediction (``mtp_modules``: models/transformer.py,
ops/head_loss.py's ``shift``) against the plain reference of the
benchmark (benchmark/reference/xing4.0-29b-a4b.py) and against the
model without it.  Float32 on the CPU at tiny widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import head_loss as hl
from tests.test_hyper_residual import (REF, TINY, apart, case, product_loss,
                                       shape_of)

SMALL = dict(TINY, num_layers=2, hyper_sinkhorn_iters=3)


def _drawn(**over):
    """(spec, params, tokens) of the SMALL model with ``over``."""
    return case(dict(SMALL, **over)).parts()


def test_the_modules_loss_is_the_references_apart_from_the_main_loss():
    spec, params, tokens = _drawn()

    @jax.jit
    def product(p):
        out = spec.apply_fn(p, tokens, True)
        return spec.loss_fn(out, tokens), out["mtp_loss"]

    total, module = product(params)
    main, mtp = jax.jit(lambda p: REF.loss(
        p, tokens, **shape_of(spec.config))[:2])(params)
    np.testing.assert_allclose(module, mtp, rtol=1e-5)
    np.testing.assert_allclose(total, main + 0.1 * mtp, rtol=1e-5)
    # another weight weighs the same module
    heavy = tfm.model_spec(**dict(SMALL, mtp_weight=0.5))
    np.testing.assert_allclose(
        jax.jit(lambda p: heavy.loss_fn(heavy.apply_fn(p, tokens, True),
                                        tokens))(params),
        main + 0.5 * mtp, rtol=1e-5)


def test_a_shifted_head_loss_is_the_cross_entropy_of_the_token_two_on():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 12, 16)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((16, 40)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 40, (2, 12)), jnp.int32)

    def plain(x, head, shift):
        logp = jax.nn.log_softmax(x[:, :-shift] @ head, axis=-1)
        return -jnp.take_along_axis(
            logp, tokens[:, shift:, None], axis=-1)[..., 0].mean(axis=-1)

    for shift in (1, 2, 3):
        got, grads = jax.value_and_grad(
            lambda x, h: hl.head_loss(x, h, tokens, shift=shift).sum(),
            argnums=(0, 1))(x, head)
        want, want_grads = jax.value_and_grad(
            lambda x, h: plain(x, h, shift).sum(), argnums=(0, 1))(x, head)
        assert float(got) == pytest.approx(float(want), rel=1e-6)
        assert apart(grads, want_grads) <= 1e-5
        # the last ``shift`` positions have no target and no gradient
        assert not float(jnp.abs(grads[0][:, -shift:]).max())


def test_the_head_and_the_embedding_take_both_paths_gradients():
    """The shared head's and the embedding's gradients are the sums of
    the main path's and the module's: each path's alone (the other's
    loss behind ``stop_gradient``) add up to the whole's."""
    spec, params, tokens = _drawn(num_layers=1)   # a layer and the module
    cfg = spec.config

    def paths(p):
        out = spec.apply_fn(p, tokens, True)
        return (tfm.head_loss(p, out["hidden"], tokens, cfg).mean(),
                cfg.mtp_weight * tfm.head_loss(
                    p, out["mtp_hidden"][0], tokens, cfg, shift=2).mean())

    @jax.jit
    def each(p):    # one forward, a pullback a path
        _, pull = jax.vjp(paths, p)
        return pull((1.0, 0.0))[0], pull((0.0, 1.0))[0]

    shared = lambda g: {"lm_head": g["lm_head"], "embed": g["embed"]}
    whole = shared(jax.jit(jax.grad(product_loss(spec, tokens)))(params))
    alone, module = each(params)
    first, second = shared(alone), shared(module)
    for name in whole:
        assert float(jnp.abs(first[name]).max()) > 0
        assert float(jnp.abs(second[name]).max()) > 0
        assert apart(first[name] + second[name], whole[name]) <= 1e-5
    # the module's own weights hear nothing of the main loss
    assert not max(float(jnp.abs(g).max())
                   for g in jax.tree_util.tree_leaves(alone["mtp"]))


def test_without_modules_the_model_is_the_parents():
    """``mtp_modules=0``: no ``mtp`` in the tree, the loss the head's
    alone, and the step statistics without the field."""
    spec = tfm.model_spec(**dict(SMALL, mtp_modules=0))
    with_module, params, tokens = _drawn()
    bare = {k: v for k, v in params.items() if k != "mtp"}
    assert jax.tree_util.tree_structure(bare) == jax.tree_util.tree_structure(
        jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0)))
    @jax.jit
    def without(p):
        out = spec.apply_fn(p, tokens, True)
        return (spec.loss_fn(out, tokens), out["hidden"],
                tfm.head_loss(p, out["hidden"], tokens, spec.config),
                spec.step_stats_fn(out))

    loss, hidden, head, stats = without(bare)
    np.testing.assert_allclose(loss, head, rtol=1e-6)
    assert "mtp_loss" not in stats and "hc_err" in stats
    # and the model with the module reaches the same hidden state
    np.testing.assert_allclose(
        jax.jit(lambda p: with_module.apply_fn(p, tokens, True)["hidden"])(
            params), hidden, rtol=1e-5, atol=1e-5)
    # evaluation's logits are the model's alone, whatever the modules
    np.testing.assert_allclose(
        jax.jit(lambda p: with_module.apply_fn(p, tokens, False))(params),
        jax.jit(lambda p: spec.apply_fn(p, tokens, False))(bare),
        rtol=1e-5, atol=1e-5)


def test_a_module_wants_the_head_loss_op():
    with pytest.raises(ValueError, match="mtp_modules"):
        tfm.model_spec(xent_chunk=8, **SMALL)


def test_the_stack_line_says_the_new_fields():
    import logging

    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    from elasticdl_tpu.ops import hyper_mix

    tfm.announce_stack.cache_clear()
    hyper_mix.announce_hyper.cache_clear()
    spec, params, tokens = _drawn(head_shares=4)
    tfm.logger.addHandler(handler)
    try:
        jax.eval_shape(product_loss(spec, tokens), params)
    finally:
        tfm.logger.removeHandler(handler)
    line, = [m for m in seen if m.startswith("layer stack:")]
    assert "heads_held=2/8 hyper=4 sinkhorn=3 mtp=1 q_latent=24" in line
    lines = [m for m in seen if m.startswith("hyper residual:")]
    assert lines == ["hyper residual: tokens=64 streams=4 width=128 "
                     "stream_bytes=131072 tile=- reference"]
