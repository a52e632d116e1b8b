"""Microbatch pipeline parallelism: parity with sequential execution.

The pipeline must be semantically invisible — same outputs, same loss,
same gradients as running the full layer stack sequentially on one
device (VERDICT r1 #4's acceptance bar).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.parallel.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)

L, E = 8, 16  # stacked layers, width


def make_params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(L, E, E).astype(np.float32) * 0.3),
        "b": jnp.asarray(rng.randn(L, E).astype(np.float32) * 0.1),
    }


def layer(x, w, b):
    return jnp.tanh(x @ w + b)


def sequential_apply(params, x):
    def body(x, wb):
        w, b = wb
        return layer(x, w, b), None

    y, _ = jax.lax.scan(body, x, (params["w"], params["b"]))
    return y


def stage_fn(stage_params, x):
    # Each stage scans its own L/S slice of the stack.
    def body(x, wb):
        w, b = wb
        return layer(x, w, b), None

    y, _ = jax.lax.scan(body, x, (stage_params["w"], stage_params["b"]))
    return y


def run_pipeline(mesh, params, x, num_microbatches, remat=False):
    xm = split_microbatches(x, num_microbatches)
    ym = pipeline_apply(
        stage_fn, params, xm, mesh=mesh,
        num_microbatches=num_microbatches, remat=remat,
    )
    return merge_microbatches(ym)


@pytest.mark.parametrize("pp,mb", [(2, 4), (4, 8), (8, 8)])
def test_forward_parity(pp, mb):
    mesh = build_mesh(pp=pp)
    params = make_params()
    x = jnp.asarray(
        np.random.RandomState(1).randn(16, E).astype(np.float32)
    )
    want = sequential_apply(params, x)
    got = run_pipeline(mesh, params, x, mb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_single_microbatch_degenerate():
    mesh = build_mesh(pp=2)
    params = make_params()
    x = jnp.ones((4, E), jnp.float32)
    got = run_pipeline(mesh, params, x, 1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(sequential_apply(params, x)),
        rtol=1e-5,
    )


@pytest.mark.parametrize("remat", [False, True])
def test_gradient_parity(remat):
    """Backward through the pipeline (autodiff of scan+ppermute) matches
    the sequential gradients — the 1F1B-equivalent drain schedule falls
    out of the transpose."""
    mesh = build_mesh(pp=4)
    params = make_params()
    x = jnp.asarray(
        np.random.RandomState(2).randn(16, E).astype(np.float32)
    )
    tgt = jnp.asarray(
        np.random.RandomState(3).randn(16, E).astype(np.float32)
    )

    def loss_seq(p):
        return jnp.mean((sequential_apply(p, x) - tgt) ** 2)

    def loss_pipe(p):
        return jnp.mean((run_pipeline(mesh, p, x, 8, remat=remat) - tgt) ** 2)

    g_seq = jax.jit(jax.grad(loss_seq))(params)
    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    for k in g_seq:
        np.testing.assert_allclose(
            np.asarray(g_pipe[k]), np.asarray(g_seq[k]),
            rtol=1e-4, atol=1e-5,
        )


def test_pipeline_with_dp_axis():
    """pp composes with dp: batch sharded over dp (auto axis), layers
    pipelined over pp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(dp=2, pp=4)
    params = make_params()
    x = jnp.asarray(
        np.random.RandomState(4).randn(16, E).astype(np.float32)
    )
    want = sequential_apply(params, x)
    xm = split_microbatches(x, 4)
    xm = jax.device_put(
        xm, NamedSharding(mesh, P(None, "dp"))
    )

    @jax.jit
    def f(params, xm):
        # x_spec only names manual axes (pp); the dp batch sharding rides
        # along as an auto axis via GSPMD.
        return pipeline_apply(
            stage_fn, params, xm, mesh=mesh, num_microbatches=4,
        )

    got = merge_microbatches(f(params, xm))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", ["dense", "gqa+window"])
def test_transformer_pipelined_matches_sequential(variant):
    """End-to-end: the flagship transformer's pipelined forward (pp=2,
    dp=2) reproduces the plain scanned forward's loss and gradients —
    incl. the GQA + sliding-window attention variants riding through
    the pipeline unchanged."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=32, num_heads=4, num_layers=4,
        max_seq_len=16, dtype="float32",
        **({"num_kv_heads": 2, "window": 8}
           if variant == "gqa+window" else {}),
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(5).randint(0, 128, size=(8, 16)),
        jnp.int32,
    )
    mesh = build_mesh(dp=2, pp=4)

    def loss_seq(p):
        return tfm.next_token_loss(
            tfm.forward(p, tokens, cfg, mesh=None), tokens
        ).mean()

    def loss_pipe(p):
        return tfm.next_token_loss(
            tfm.forward_pipelined(p, tokens, cfg, mesh, 4), tokens
        ).mean()

    l_seq, g_seq = jax.jit(jax.value_and_grad(loss_seq))(params)
    l_pipe, g_pipe = jax.jit(jax.value_and_grad(loss_pipe))(params)
    np.testing.assert_allclose(float(l_pipe), float(l_seq), rtol=1e-5)
    flat_seq = jax.tree_util.tree_leaves(g_seq)
    flat_pipe = jax.tree_util.tree_leaves(g_pipe)
    for a, b in zip(flat_pipe, flat_seq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


def test_transformer_pipelined_rejects_sp():
    from elasticdl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, dim=16, num_heads=2,
                                num_layers=2, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(pp=2, sp=2, dp=2)
    tokens = jnp.zeros((4, 8), jnp.int32)
    with pytest.raises(ValueError, match="sp=1"):
        tfm.forward_pipelined(params, tokens, cfg, mesh, 2)


def test_pipelined_moe_aux_matches_sequential():
    """The pipelined path recovers the EXACT full-batch MoE aux by
    accumulating linear router statistics (bubble ticks masked) —
    identical objective to the scanned forward, at any M."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=32, num_heads=4, num_layers=4,
        max_seq_len=16, dtype="float32", moe_experts=4, moe_top_k=2,
    )
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(7).randint(0, 128, size=(8, 16)),
        jnp.int32,
    )
    mesh = build_mesh(dp=2, pp=4)
    logits_seq, aux_seq = tfm.forward(params, tokens, cfg,
                                      return_aux=True)
    logits_pipe, aux_pipe = jax.jit(
        lambda p, t: tfm.forward_pipelined(
            p, t, cfg, mesh, 4, return_aux=True
        )
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(logits_pipe),
                               np.asarray(logits_seq),
                               rtol=5e-4, atol=1e-5)
    # The pipeline accumulates the LINEAR router statistics, so its
    # aux equals the full-batch Switch value EXACTLY — same objective
    # regardless of the microbatch count.
    np.testing.assert_allclose(float(aux_pipe), float(aux_seq),
                               rtol=1e-4)
    aux_pipe_m2 = jax.jit(
        lambda p, t: tfm.forward_pipelined(
            p, t, cfg, mesh, 2, return_aux=True
        )
    )(params, tokens)[1]
    np.testing.assert_allclose(float(aux_pipe_m2), float(aux_seq),
                               rtol=1e-4)


def test_pipelined_moe_grad_parity_through_aux():
    """Backward through the tree-aux accumulation + finalize: gradients
    of (task loss + aux) on the pipelined path match the sequential
    forward's — including the router, which only the aux reaches."""
    from elasticdl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, dim=32, num_heads=4, num_layers=4,
        max_seq_len=8, dtype="float32", moe_experts=4, moe_top_k=2,
    )
    params = tfm.init_params(jax.random.PRNGKey(9), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(9).randint(0, 64, size=(8, 8)), jnp.int32
    )
    mesh = build_mesh(dp=2, pp=4)

    def loss_seq(p):
        logits, aux = tfm.forward(p, tokens, cfg, return_aux=True)
        return tfm.next_token_loss(logits, tokens).mean() + 0.01 * aux

    def loss_pipe(p):
        logits, aux = tfm.forward_pipelined(
            p, tokens, cfg, mesh, 4, return_aux=True
        )
        return tfm.next_token_loss(logits, tokens).mean() + 0.01 * aux

    g_seq = jax.jit(jax.grad(loss_seq))(params)
    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    router_grad = np.asarray(g_pipe["layers"]["w_router"])
    assert np.abs(router_grad).max() > 0, "router got no gradient"
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-6)
